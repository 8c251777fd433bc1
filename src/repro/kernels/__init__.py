# The TPU Pallas kernel for this system's compute hot-spot:
#   crc32.py           — batch object/shard CRC verification (the paper's §4.2
#                        verify step, restructured lane-parallel for the VPU)
# ops.py holds the jit'd public wrappers; ref.py the pure-jnp oracle.
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
