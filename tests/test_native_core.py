"""C++ host core: fingerprint-mixer parity and the concurrent visited set.

Reference analog: the stable hasher (src/lib.rs:340-387) and the
lock-sharded visited DashMap (src/checker/bfs.rs:29-31), implemented
natively in native/stateright_core.cpp per the survey's stack decision.
"""

import threading

import numpy as np
import pytest

from stateright_tpu.ops.fingerprint import _py_fp64_words, fingerprint
from stateright_tpu.ops.native import (
    NativeFpSet,
    available,
    fp64_words_native,
)

pytestmark = pytest.mark.skipif(
    not available(), reason="no C++ toolchain for the native core"
)


def test_mixer_bit_identical_to_python():
    rng = np.random.default_rng(11)
    for n in (0, 1, 2, 15, 16, 17, 100, 1000):
        words = rng.integers(0, 2**32, n, dtype=np.uint32).tolist()
        assert fp64_words_native(words) == _py_fp64_words(words)


def test_batch_mixer_matches_python():
    from stateright_tpu.ops.native import fp64_batch_native

    rng = np.random.default_rng(5)
    m = rng.integers(0, 2**32, size=(64, 7), dtype=np.uint32)
    got = fp64_batch_native(m)
    assert got == [_py_fp64_words(row.tolist()) for row in m]


def test_fingerprint_dispatch_consistent():
    # Values small and large enough to cross the native-dispatch threshold
    # must produce identical digests either way.
    values = [
        (1, 2, 3),
        tuple(range(50)),
        frozenset(range(40)),
        ("str", (True, None, 3.5), b"bytes" * 20),
    ]
    for v in values:
        from stateright_tpu.ops import fingerprint as fp_mod

        words = []
        fp_mod.canon_words(v, words)
        assert fingerprint(v) == _py_fp64_words(words)


def test_fpset_matches_dict():
    import random

    rng = random.Random(3)
    s = NativeFpSet(1 << 12)
    ref = {}
    for _ in range(2000):
        fp = rng.randrange(1, 1 << 20)
        parent = rng.randrange(1, 1 << 40)
        inserted = s.insert(fp, parent)
        assert inserted == (fp not in ref)
        if inserted:
            ref[fp] = parent
    assert len(s) == len(ref)
    for fp, parent in list(ref.items())[:300]:
        assert fp in s
        assert s.parent(fp) == parent
    assert (1 << 21) + 1 not in s
    assert s.parent((1 << 21) + 1) is None


def test_fpset_concurrent_inserts():
    s = NativeFpSet(1 << 16)

    def worker(tag):
        for i in range(5000):
            s.insert(i + 1, tag + 1)

    threads = [
        threading.Thread(target=worker, args=(k,)) for k in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # All threads insert the same 5000 keys; exactly one wins each.
    assert len(s) == 5000
    assert all((i + 1) in s for i in range(0, 5000, 97))


def test_fpset_grows_past_initial_capacity():
    # DashMap-style: 3/4 load doubles the table, so a tiny initial
    # capacity accepts arbitrarily many keys and keeps every parent.
    s = NativeFpSet(1 << 4)
    for i in range(5000):
        assert s.insert(i + 1, i + 100)
    assert len(s) == 5000
    for i in range(0, 5000, 113):
        assert (i + 1) in s
        assert s.parent(i + 1) == i + 100
    assert 999999 not in s


def test_fpset_concurrent_inserts_across_growth():
    s = NativeFpSet(1 << 4)  # forces many growths under contention

    def worker(tag):
        for i in range(4000):
            s.insert(i + 1, tag + 1)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(s) == 4000
    assert all((i + 1) in s for i in range(0, 4000, 59))


def test_graph_engine_uses_native_set_when_threaded():
    # threads > 1 routes the visited set through the C++ facade; counts,
    # discovery sets, and reconstructed paths must match the dict engine.
    from stateright_tpu.core.engine import _NativeGenerated
    from stateright_tpu.models.ping_pong import PingPongCfg

    model = PingPongCfg(maintains_history=True, max_nat=5).into_model()
    threaded = model.checker().threads(2).spawn_bfs().join()
    assert isinstance(threaded._generated, _NativeGenerated)
    single = model.checker().spawn_bfs().join()
    assert not isinstance(single._generated, _NativeGenerated)
    assert threaded.unique_state_count() == single.unique_state_count()
    assert set(threaded.discoveries()) == set(single.discoveries())
    for name, path in threaded.discoveries().items():
        assert path.last_state() is not None

    dfs = model.checker().threads(2).spawn_dfs().join()
    assert dfs.unique_state_count() == single.unique_state_count()


def test_twophase_native_bfs_reference_goldens():
    """The C++ hot-loop BFS explores exactly the direct 2pc reachable space: reference goldens
    288 (3 RMs) and 8,832 (5 RMs, examples/2pc.rs:151-159), with the
    framework's depth convention and generated-state counts."""
    from stateright_tpu.models.twophase import TwoPhaseSys
    from stateright_tpu.ops.native import twophase_bfs_native

    host = TwoPhaseSys(rm_count=3).checker().spawn_bfs().join()
    r = twophase_bfs_native(3)
    assert r["unique_states"] == host.unique_state_count() == 288
    assert r["generated"] == host.state_count()
    assert r["max_depth"] == host.max_depth()

    assert twophase_bfs_native(5)["unique_states"] == 8_832


def test_twophase_native_bfs_guards():
    from stateright_tpu.ops.native import twophase_bfs_native

    with pytest.raises(RuntimeError, match="rc="):
        twophase_bfs_native(13)  # past the packed layout's 12-RM bound
    with pytest.raises(RuntimeError, match="rc="):
        twophase_bfs_native(5, max_unique=100)  # memory guard trips


def test_library_is_keyed_on_source_content(tmp_path, monkeypatch):
    """A library built from other source is never reused, whatever the
    mtimes say: a copied tree can carry a .so newer than a changed
    source."""
    import os

    from stateright_tpu.ops import native

    src = tmp_path / "stateright_core.cpp"
    monkeypatch.setattr(native, "_SRC", tmp_path)
    monkeypatch.setattr(native, "_OUT_DIR", tmp_path)

    src.write_text('extern "C" int sr_probe() { return 1; }\n')
    first = native._compile()
    assert first is not None and first.exists()
    assert native._compile() == first  # same content: reused

    src.write_text('extern "C" int sr_probe() { return 2; }\n')
    # The stale library looks newer than the changed source.
    stamp = first.stat().st_mtime
    os.utime(src, (stamp - 60, stamp - 60))
    second = native._compile()
    assert second is not None and second != first
    assert second.exists() and first.exists()
