"""Flru, the open-segment fd cap, and io metrics (the reference's
ra_flru.erl, ra_log_reader open_segments, and ra_file_handle roles)."""

from ra_tpu.core.types import Entry, ServerConfig, ServerId
from ra_tpu.core.machine import SimpleMachine
from ra_tpu.native import IO
from ra_tpu.utils.flru import Flru


def test_flru_eviction_order_and_handler():
    evicted = []
    lru = Flru(3, on_evict=lambda k, v: evicted.append(k))
    for k in "abc":
        lru.touch(k, k.upper())
    lru.touch("a", "A")          # refresh: a is now MRU
    lru.touch("d", "D")          # evicts b (the LRU)
    assert evicted == ["b"]
    assert "a" in lru and "b" not in lru
    lru.touch("e", "E")          # evicts c
    assert evicted == ["b", "c"]
    assert len(lru) == 3


def test_flru_pop_skips_handler_and_evict_all():
    evicted = []
    lru = Flru(4, on_evict=lambda k, v: evicted.append(k))
    for k in "abcd":
        lru.touch(k, k)
    assert lru.pop("b") == "b"
    assert evicted == []
    lru.evict_all()
    assert sorted(evicted) == ["a", "c", "d"]
    assert len(lru) == 0


def _mk_log(system, uid):
    cfg = ServerConfig(server_id=ServerId(uid, "n1"), uid=uid,
                       cluster_name="flru",
                       initial_members=(ServerId(uid, "n1"),),
                       machine=SimpleMachine(lambda c, s: s, 0))
    return system.log_factory(cfg)


def _settle(system, log):
    system.wal.flush()
    system.segment_writer.await_idle()
    for evt in log.take_events():
        log.handle_written(evt)


def test_open_segment_fds_are_capped(tmp_path):
    from ra_tpu import RaSystem
    from ra_tpu.log.durable import MAX_OPEN_SEGMENTS

    system = RaSystem(str(tmp_path / "d"), segment_max_count=8)
    log = _mk_log(system, "uid_cap")
    try:
        # 96 entries over 8-entry segments -> 12 segment files
        for i in range(1, 97):
            log.write([Entry(i, 1, f"e{i}")])
            if i % 8 == 0:
                system.wal.rollover()
                _settle(system, log)
        _settle(system, log)
        assert len(log._segments) >= 10
        open_fds = sum(1 for s in log._segments if s.fd is not None)
        assert open_fds <= MAX_OPEN_SEGMENTS
        # reads across ALL segments still work (evicted ones reopen),
        # and the cap holds afterwards
        for i in range(1, 97):
            ent = log.fetch(i)
            assert ent is not None and ent.command == f"e{i}"
        open_fds = sum(1 for s in log._segments if s.fd is not None)
        assert open_fds <= MAX_OPEN_SEGMENTS
    finally:
        system.close()


def test_reopen_after_restart_respects_cap(tmp_path):
    from ra_tpu import RaSystem
    from ra_tpu.log.durable import MAX_OPEN_SEGMENTS

    data = str(tmp_path / "d2")
    system = RaSystem(data, segment_max_count=8)
    log = _mk_log(system, "uid_cap2")
    for i in range(1, 81):
        log.write([Entry(i, 1, f"e{i}")])
        if i % 8 == 0:
            system.wal.rollover()
            _settle(system, log)
    _settle(system, log)
    system.close()
    system2 = RaSystem(data, segment_max_count=8)
    log2 = _mk_log(system2, "uid_cap2")
    try:
        assert log2.last_index_term().index == 80
        open_fds = sum(1 for s in log2._segments if s.fd is not None)
        assert open_fds <= MAX_OPEN_SEGMENTS
        assert log2.fetch(1).command == "e1"
    finally:
        system2.close()


def test_io_stats_observe_traffic(tmp_path):
    from ra_tpu import RaSystem

    before = IO.stats()
    system = RaSystem(str(tmp_path / "d3"))
    log = _mk_log(system, "uid_io")
    try:
        log.write([Entry(i, 1, b"x" * 64) for i in range(1, 33)])
        _settle(system, log)
        after = IO.stats()
        assert after["writes"] > before["writes"]
        assert after["write_bytes"] > before["write_bytes"]
        assert after["syncs"] > before["syncs"]
        assert set(after) == {"reads", "read_bytes", "writes",
                              "write_bytes", "syncs", "opens"}
    finally:
        system.close()


def test_overview_exposes_io(tmp_path):
    import ra_tpu
    from ra_tpu.node import LocalRouter

    router = LocalRouter()
    ov = ra_tpu.overview(router=router)
    assert "writes" in ov["io"]
    assert ov["nodes"] == {}


def test_native_library_is_keyed_by_source_content(tmp_path):
    """The library a process loads is named by wal_native.cpp's content
    hash, so a binary left by another source revision (or carried into
    a copied tree under the old fixed name) can never be picked up."""
    import hashlib
    import os

    from ra_tpu import native

    with open(native._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = native._so_path()
    assert os.path.basename(so) == f"libra_wal-{digest}.so"
    if IO.native:
        assert IO.lib._name == so and native.BUILD_ERROR is None
    else:
        assert native.BUILD_ERROR     # a fallback always says why
