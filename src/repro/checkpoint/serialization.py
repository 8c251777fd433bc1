"""Array leaf ↔ bytes with a self-describing header (dtype, shape)."""
from __future__ import annotations

import json
import struct

import numpy as np


def _resolve_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # bfloat16 & friends
        return np.dtype(getattr(ml_dtypes, name))


def _meta(dtype, shape) -> bytes:
    return json.dumps({"dtype": np.dtype(dtype).name,
                       "shape": [int(d) for d in shape]}).encode()


def leaf_to_bytes(arr) -> bytes:
    a = np.asarray(arr)
    meta = _meta(a.dtype, a.shape)
    return struct.pack("<I", len(meta)) + meta + a.tobytes()


def encoded_size(shape, dtype) -> int:
    """Length of ``leaf_to_bytes`` of an array of this shape and dtype."""
    return (4 + len(_meta(dtype, shape))
            + int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize)


def leaf_from_bytes(buf: bytes) -> np.ndarray:
    (mlen,) = struct.unpack_from("<I", buf, 0)
    meta = json.loads(bytes(buf[4 : 4 + mlen]).decode())
    return np.frombuffer(buf, dtype=_resolve_dtype(meta["dtype"]),
                         offset=4 + mlen).reshape(meta["shape"]).copy()
