"""The in-program tracer (``repro.obs``): off it records nothing; on it
records nested spans with their parents, attributes and self time into a
bounded ring; and the page store's snapshot and restore emit a span at each
layer boundary without changing what the store does."""
import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.core import ServerConfig, make_store

SMALL = ServerConfig(device_size=16 << 20, table_capacity=1 << 10, n_heads=2,
                     region_size=1 << 20, segment_size=64 << 10)


@pytest.fixture
def tracing():
    obs.enable()
    try:
        yield
    finally:
        obs.disable()


def _names(evs):
    return [e.name for e in evs]


def test_off_records_nothing_and_shares_one_context():
    assert not obs.TRACER.on
    a, b = obs.span("x", seq_id=1), obs.span("y", nbytes=2)
    assert a is b
    before = obs.TRACER.recorded
    with a as sp:
        sp.set(nbytes=3)
    assert obs.TRACER.recorded == before


def test_nested_spans_parents_attrs_and_self_time(tracing):
    with obs.span("outer", seq_id=7):
        with obs.span("a", nbytes=10):
            pass
        with obs.span("b") as sp:
            with obs.span("c"):
                pass
            sp.set(nbytes=20)
    evs, dropped = obs.events()
    assert dropped == 0
    by = {e.name: e for e in evs}
    assert _names(evs) == ["a", "c", "b", "outer"]  # the order they closed
    assert by["outer"].parent is None
    assert by["a"].parent == by["b"].parent == by["outer"].id
    assert by["c"].parent == by["b"].id
    assert by["outer"].attrs == {"seq_id": 7}
    assert by["a"].attrs == {"nbytes": 10} and by["b"].attrs == {"nbytes": 20}
    for e in evs:
        assert e.t0 <= e.t1
    own = obs.self_seconds(evs)
    o = by["outer"]
    assert own[o.id] == pytest.approx(
        (o.t1 - o.t0) - (by["a"].t1 - by["a"].t0) - (by["b"].t1 - by["b"].t0))
    assert own[by["c"].id] == pytest.approx(by["c"].t1 - by["c"].t0)
    # a window selects by start time
    assert _names(obs.events(by["b"].t0, by["b"].t1)[0]) == ["c", "b"]


def test_self_time_subtracts_the_union_of_children():
    E = obs.Event
    evs = [E(0, "p", 0.0, 10.0, None, {}), E(1, "c", 1.0, 4.0, 0, {}),
           E(2, "c", 3.0, 5.0, 0, {}), E(3, "c", 8.0, 9.0, 0, {})]
    assert obs.self_seconds(evs)[0] == pytest.approx(10 - 4 - 1)


def test_ring_keeps_the_newest_and_counts_drops(monkeypatch, tracing):
    monkeypatch.setattr(obs.TRACER, "capacity", 8)
    obs.enable()  # a ring of the new capacity
    for i in range(13):
        with obs.span("s", seq_id=i):
            pass
    evs, dropped = obs.events()
    assert dropped == 5
    assert [e.attrs["seq_id"] for e in evs] == list(range(5, 13))
    obs.enable()
    assert obs.events() == ([], 0)


def test_enable_does_not_break_spans_already_open():
    obs.enable()
    try:
        with obs.span("outer"):
            obs.enable()
            with obs.span("inner"):
                pass
        evs, _ = obs.events()
        assert _names(evs) == ["inner", "outer"]
    finally:
        obs.disable()


def _cache():
    rng = np.random.default_rng(0)
    import jax.numpy as jnp
    k = rng.standard_normal((2, 2, 48, 4, 32)).astype(np.float32)  # 96 KiB
    return {"pos": jnp.int32(7), "k": jnp.asarray(k, jnp.bfloat16),
            "v": jnp.asarray(k)}


def _descendants(evs, root):
    kids = {}
    for e in evs:
        kids.setdefault(e.parent, []).append(e)
    out, todo = [], [root.id]
    while todo:
        for e in kids.get(todo.pop(), ()):
            out.append(e)
            todo.append(e.id)
    return out


def test_page_store_snapshot_and_restore_emit_each_layer(tracing):
    import jax
    from repro.serving.kv_store import ErdaKVPageStore
    pages = ErdaKVPageStore(make_store("erda-cluster", n_shards=2, cfg=SMALL))
    cache = _cache()
    n_leaves = len(jax.tree.leaves(cache))
    n_pages = pages.snapshot_cache(3, cache)
    got = pages.restore_cache(3, jax.eval_shape(lambda: cache))
    for want, have in zip(jax.tree.leaves(cache), jax.tree.leaves(got)):
        assert np.asarray(have).tobytes() == np.asarray(want).tobytes()
    evs, dropped = obs.events()
    assert dropped == 0
    by_id = {e.id: e for e in evs}
    snap = [e for e in evs if e.name == "pages.snapshot"]
    assert len(snap) == 1 and snap[0].attrs["seq_id"] == 3
    under = _descendants(evs, snap[0])
    count = {n: _names(under).count(n) for n in set(_names(under))}
    assert count["pages.to_host"] == count["pages.encode"] == n_leaves
    assert count["store.multi_write"] == 1
    assert count["client.pack"] == count["server.write_req"] == n_pages
    assert count["nvm.write"] >= n_pages
    assert count["nvm.dcw"] == count["nvm.write"]

    def parent(e):
        return by_id[e.parent].name

    for e in under:
        want = {"pages.to_host": "pages.snapshot",
                "pages.encode": "pages.snapshot",
                "store.multi_write": "pages.snapshot",
                "nvm.dcw": "nvm.write"}.get(e.name)
        if want:
            assert parent(e) == want
        elif e.name in ("client.pack", "server.write_req"):
            assert parent(e) == "store.multi_write"
        else:
            assert e.name == "nvm.write"
            assert parent(e) in ("store.multi_write", "server.write_req")
    writes = [e for e in evs if e.name == "store.multi_write"]
    assert (sum(e.attrs["nbytes"] for e in writes)
            == pages.counters["snapshot_bytes"] == snap[0].attrs["nbytes"])
    to_host = sum(e.attrs["nbytes"] for e in under
                  if e.name == "pages.to_host")
    assert to_host == sum(leaf.nbytes for leaf in jax.tree.leaves(cache))
    # every page stored passes through the NVM write and its DCW accounting
    data = [e for e in under if e.name == "nvm.write"
            and parent(e) == "store.multi_write"]
    assert len(data) == n_pages

    restore = [e for e in evs if e.name == "pages.restore"]
    assert len(restore) == 1 and restore[0].attrs["seq_id"] == 3
    below = _descendants(evs, restore[0])
    assert _names(below).count("pages.decode") == n_leaves
    assert _names(below).count("store.multi_read") == 1
    assert _names(below).count("client.verify") == n_pages
    assert (sum(e.attrs["nbytes"] for e in below if e.name == "pages.decode")
            == restore[0].attrs["nbytes"] == pages.counters["snapshot_bytes"])


def _census(on: bool):
    """Verb, doorbell and NVM counts of one snapshot, restore, page write and
    read, with the tracer on or off."""
    import jax
    from repro.serving.kv_store import ErdaKVPageStore
    store = make_store("erda-cluster", n_shards=2, cfg=SMALL)
    pages = ErdaKVPageStore(store)
    if on:
        obs.enable()
    try:
        cache = _cache()
        pages.snapshot_cache(1, cache)
        pages.snapshot_cache(1, cache)  # an overwrite: DCW sees old bytes
        pages.put_page(1, "__tokens__", 0, np.arange(12).reshape(4, 3))
        pages.restore_cache(1, jax.eval_shape(lambda: cache))
        pages.get_page(1, "__tokens__", 0)
    finally:
        obs.disable()
    clients = store.cluster.clients
    return ([dict(c.transport.counts) for c in clients],
            [c.transport.doorbells for c in clients],
            [dataclasses.asdict(d.stats) for d in store.devs],
            pages.stats)


def test_censuses_are_the_same_with_tracing_on_and_off():
    off = _census(False)
    on = _census(True)
    assert on == off
    assert sum(d["bytes_programmed"] for d in off[2]) > 0
