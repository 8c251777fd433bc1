"""Property tests for the system's core invariants.

Hypothesis-driven versions run when ``hypothesis`` is installed; a seeded
random smoke suite covering the same properties always runs, so tier-1 never
loses this coverage (and never dies at collection) on a machine without the
dependency.
"""
import numpy as np
import pytest

from repro.core import ErdaStore, ServerConfig, make_store
from repro.core import layout
from repro.nvmsim.device import TornWrite

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # tier-1 must still collect: smoke fallbacks below cover us
    HAVE_HYPOTHESIS = False


def small_store():
    return ErdaStore(ServerConfig(device_size=64 << 20, table_capacity=1 << 12,
                                  n_heads=2, region_size=1 << 20, segment_size=32 << 10))


def small_cluster():
    return make_store("erda-cluster", n_shards=4,
                      cfg=ServerConfig(device_size=16 << 20, table_capacity=1 << 10,
                                       n_heads=2, region_size=1 << 20,
                                       segment_size=32 << 10))


# ---------------------------------------------------------------- model checks
def check_matches_dict_model(store, ops):
    model = {}
    for op, k, v in ops:
        if op == "read":
            assert store.read(k) == model.get(k)
        elif op == "write":
            store.write(k, v)
            model[k] = v
        else:
            if k in model:
                store.delete(k)
                model.pop(k)
    for k, v in model.items():
        assert store.read(k) == v


def check_torn_write_invariant(store, dev, ops, tear_at, fraction):
    """THE paper invariant: inject one torn data write anywhere in an op
    stream; every subsequent read returns either the pre-tear value or a
    post-tear written value — never garbage, never a partial object.

    A tear whose unwritten tail already held the record's bytes leaves the
    whole record on NVM (say, a tail of zeros over zeroed NVM): its CRC
    passes and the new value is the one read."""
    model = {}
    writes_seen = 0
    for op, k, v in ops:
        if op == "write":
            if writes_seen == tear_at:
                dev.fault.arm(countdown=0, fraction=fraction)
                try:
                    store.write(k, v)
                    model[k] = v  # tear hit a different (e.g. metadata) spot
                except TornWrite as e:
                    rec = layout.pack_record(k, v)
                    on_nvm = bytes(dev.mem[e.addr : e.addr + e.requested])
                    if e.requested == len(rec) and on_nvm == rec:
                        model[k] = v  # the record is whole after all
                    # otherwise the model keeps the OLD value for k
                writes_seen += 1
                continue
            writes_seen += 1
            store.write(k, v)
            model[k] = v
        elif op == "read":
            assert store.read(k) == model.get(k)
        else:
            if k in model:
                store.delete(k)
                model.pop(k)
    for k, v in model.items():
        assert store.read(k) == v


def random_ops(rng, n):
    ops = []
    for _ in range(n):
        kind = ("read", "write", "delete")[int(rng.integers(3))]
        k = int(rng.integers(1, 25))
        v = rng.bytes(int(rng.integers(0, 201)))
        ops.append((kind, k, v))
    return ops


def check_multi_ops_equiv_sequential(store_batched, store_seq, rounds):
    """multi_read / multi_write must be observationally equivalent to the
    sequential loop: drive two identical stores, one through the batched
    ops, one op-at-a-time, and compare every result + the full final state."""
    model = {}
    touched = set()
    for kind, payload in rounds:
        if kind == "write":
            store_batched.multi_write(payload)
            for k, v in payload:
                store_seq.write(k, v)
                model[k] = v
                touched.add(k)
        else:
            touched.update(payload)
            got_b = store_batched.multi_read(payload)
            got_s = [store_seq.read(k) for k in payload]
            assert got_b == got_s == [model.get(k) for k in payload]
    keys = sorted(touched)
    assert store_batched.multi_read(keys) == [store_seq.read(k) for k in keys] \
        == [model.get(k) for k in keys]


def random_multi_rounds(rng, n_rounds):
    rounds = []
    for _ in range(n_rounds):
        size = int(rng.integers(1, 12))
        if rng.random() < 0.5:
            rounds.append(("write", [(int(k), rng.bytes(int(rng.integers(0, 150))))
                                     for k in rng.integers(1, 30, size=size)]))
        else:
            rounds.append(("read", [int(k) for k in rng.integers(1, 35, size=size)]))
    return rounds


# ------------------------------------------------------------ hypothesis suite
if HAVE_HYPOTHESIS:

    @given(st.binary(min_size=0, max_size=2048), st.integers(min_value=1, max_value=2**62))
    @settings(max_examples=60, deadline=None)
    def test_record_roundtrip(value, key):
        rec = layout.pack_record(key, value)
        view = layout.parse_record(np.frombuffer(rec, dtype=np.uint8))
        assert view.ok and view.key == key and view.value == value

    @given(st.binary(min_size=1, max_size=512), st.integers(min_value=0, max_value=10**6))
    @settings(max_examples=60, deadline=None)
    def test_any_truncation_detected(value, seed):
        """RDA invariant: any proper prefix of a record fails verification —
        unless the zero-fill happens to reproduce the record bit-for-bit (a value
        with trailing zeros), in which case there is no tear to detect."""
        rec = layout.pack_record(7, value)
        cut = int(np.random.default_rng(seed).integers(0, len(rec)))
        torn = rec[:cut] + b"\x00" * (len(rec) - cut)
        if torn == rec:
            return  # bitwise identical: semantically complete
        assert not layout.parse_record(np.frombuffer(torn, dtype=np.uint8)).ok

    @given(st.integers(min_value=0, max_value=1), st.integers(min_value=0, max_value=2**31 - 1),
           st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=100, deadline=None)
    def test_word_roundtrip(tag, off_new, off_old):
        assert layout.unpack_word(layout.pack_word(tag, off_new, off_old)) == (tag, off_new, off_old)

    @given(st.integers(min_value=0, max_value=2**31 - 2),
           st.integers(min_value=0, max_value=2**31 - 2),
           st.integers(min_value=0, max_value=2**31 - 2))
    @settings(max_examples=100, deadline=None)
    def test_flip_preserves_previous_new_as_old(initial, first, second):
        w = layout.pack_word(1, initial, layout.NULL_OFF)
        w = layout.flip_word(w, first)
        _, new, old = layout.unpack_word(w)
        assert (new, old) == (first, initial)
        w = layout.flip_word(w, second)
        _, new, old = layout.unpack_word(w)
        assert (new, old) == (second, first)

    ops_strategy = st.lists(
        st.tuples(st.sampled_from(["read", "write", "delete"]),
                  st.integers(min_value=1, max_value=24),
                  st.binary(min_size=0, max_size=200)),
        min_size=1, max_size=120,
    )

    @given(ops_strategy)
    @settings(max_examples=40, deadline=None, suppress_health_check=[HealthCheck.too_slow])
    def test_erda_matches_dict_model(ops):
        check_matches_dict_model(small_store(), ops)

    @given(ops_strategy)
    @settings(max_examples=15, deadline=None, suppress_health_check=[HealthCheck.too_slow])
    def test_cluster_matches_dict_model(ops):
        check_matches_dict_model(small_cluster(), ops)

    @given(ops_strategy, st.integers(min_value=0, max_value=30),
           st.floats(min_value=0.0, max_value=0.95))
    @settings(max_examples=30, deadline=None, suppress_health_check=[HealthCheck.too_slow])
    def test_torn_write_never_corrupts_observable_state(ops, tear_at, fraction):
        s = small_store()
        check_torn_write_invariant(s, s.dev, ops, tear_at, fraction)

    multi_rounds_strategy = st.lists(
        st.one_of(
            st.tuples(st.just("write"),
                      st.lists(st.tuples(st.integers(min_value=1, max_value=29),
                                         st.binary(min_size=0, max_size=150)),
                               min_size=1, max_size=11)),
            st.tuples(st.just("read"),
                      st.lists(st.integers(min_value=1, max_value=34),
                               min_size=1, max_size=11)),
        ),
        min_size=1, max_size=16,
    )

    @given(multi_rounds_strategy)
    @settings(max_examples=20, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def test_erda_multi_ops_equiv_sequential(rounds):
        check_multi_ops_equiv_sequential(small_store(), small_store(), rounds)

    @given(multi_rounds_strategy)
    @settings(max_examples=10, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def test_cluster_multi_ops_equiv_sequential(rounds):
        check_multi_ops_equiv_sequential(small_cluster(), small_cluster(), rounds)

    @given(st.integers(min_value=1, max_value=200))
    @settings(max_examples=20, deadline=None)
    def test_cleaning_idempotent_contents(n_keys):
        s = ErdaStore(ServerConfig(device_size=128 << 20, table_capacity=1 << 12,
                                   n_heads=1, region_size=1 << 20, segment_size=32 << 10))
        model = {}
        for k in range(1, n_keys + 1):
            v = bytes([k % 256]) * (k % 97 + 1)
            s.write(k, v)
            s.write(k, v[::-1])
            model[k] = v[::-1]
        c = s.server.start_cleaning(0)
        c.run_to_completion()
        for k, v in model.items():
            assert s.read(k) == v


# --------------------------------------------------- seeded smoke fallbacks
# Same properties, driven by numpy RNG: always collected, no hypothesis needed.

def test_smoke_record_roundtrip():
    rng = np.random.default_rng(0)
    for _ in range(60):
        key = int(rng.integers(1, 2**62))
        value = rng.bytes(int(rng.integers(0, 2049)))
        rec = layout.pack_record(key, value)
        view = layout.parse_record(np.frombuffer(rec, dtype=np.uint8))
        assert view.ok and view.key == key and view.value == value


def test_smoke_any_truncation_detected():
    rng = np.random.default_rng(1)
    for _ in range(60):
        value = rng.bytes(int(rng.integers(1, 513)))
        rec = layout.pack_record(7, value)
        cut = int(rng.integers(0, len(rec)))
        torn = rec[:cut] + b"\x00" * (len(rec) - cut)
        if torn == rec:
            continue
        assert not layout.parse_record(np.frombuffer(torn, dtype=np.uint8)).ok


def test_smoke_word_roundtrip_and_flip():
    rng = np.random.default_rng(2)
    for _ in range(100):
        tag = int(rng.integers(0, 2))
        off_new, off_old = (int(rng.integers(0, 2**31)) for _ in range(2))
        assert layout.unpack_word(layout.pack_word(tag, off_new, off_old)) \
            == (tag, off_new, off_old)
    for _ in range(100):
        initial, first, second = (int(rng.integers(0, 2**31 - 1)) for _ in range(3))
        w = layout.pack_word(1, initial, layout.NULL_OFF)
        w = layout.flip_word(w, first)
        assert layout.unpack_word(w)[1:] == (first, initial)
        w = layout.flip_word(w, second)
        assert layout.unpack_word(w)[1:] == (second, first)


@pytest.mark.parametrize("store_maker", [small_store, small_cluster],
                         ids=["erda", "erda-cluster"])
def test_smoke_matches_dict_model(store_maker):
    rng = np.random.default_rng(3)
    for trial in range(8):
        check_matches_dict_model(store_maker(), random_ops(rng, 120))


@pytest.mark.parametrize("store_maker", [small_store, small_cluster],
                         ids=["erda", "erda-cluster"])
def test_smoke_multi_ops_equiv_sequential(store_maker):
    rng = np.random.default_rng(5)
    for trial in range(5):
        check_multi_ops_equiv_sequential(store_maker(), store_maker(),
                                         random_multi_rounds(rng, 12))


def test_smoke_torn_write_never_corrupts_observable_state():
    rng = np.random.default_rng(4)
    for trial in range(10):
        s = small_store()
        ops = random_ops(rng, 80)
        tear_at = int(rng.integers(0, 31))
        fraction = float(rng.random() * 0.95)
        check_torn_write_invariant(s, s.dev, ops, tear_at, fraction)


def test_torn_write_that_leaves_the_record_whole_reads_the_new_value():
    """The tear persists 3/4 of the third record; its unwritten tail is zeros
    over zeroed NVM, so the record is whole and the new value is read."""
    s = small_store()
    ops = [("write", 1, b""), ("write", 1, b""), ("write", 1, b"\x00")]
    check_torn_write_invariant(s, s.dev, ops, 2, 0.75)
    assert s.read(1) == b"\x00"


def test_smoke_cleaning_idempotent_contents():
    s = ErdaStore(ServerConfig(device_size=128 << 20, table_capacity=1 << 12,
                               n_heads=1, region_size=1 << 20, segment_size=32 << 10))
    model = {}
    for k in range(1, 151):
        v = bytes([k % 256]) * (k % 97 + 1)
        s.write(k, v)
        s.write(k, v[::-1])
        model[k] = v[::-1]
    s.server.start_cleaning(0).run_to_completion()
    for k, v in model.items():
        assert s.read(k) == v
