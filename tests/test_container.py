"""repro.container: partitioned vectors + segmented algorithms over a
3-locality runtime (block, cyclic and explicit layouts — including empty
and single-element segments), every algorithm checked against the
single-locality seq oracle, plus the counter-verified work-to-data claim:
``for_each`` moves ~zero element bytes while fetch-all moves them all.

Bodies/ops live at module level: segmented algorithms ship them to the
data pickled by reference."""

import itertools
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import net as rnet
from repro.core import algorithms as alg
from repro.core.executor import par, par_task, seq
from repro.core.future import Future
from repro.container import PartitionedVector, distribution as dist_mod


# ----------------------------------------------------- module-level bodies
def aff(x):
    return 3 * x + 1


def sq(x):
    return x * x


def is_even(x):
    return x % 2 == 0


def nonneg(x):
    return x >= 0


def touch(x):
    pass


def attach_probe(rt, name):
    """Runs on a worker: attach by name and read through the handle."""
    pv = PartitionedVector.attach(name)
    return [len(pv), pv.nsegments, float(pv.get(0))]


# ------------------------------------------------------------------ fixture
@pytest.fixture(scope="module")
def net(rt):
    with rnet.running(3, pools={"default": 4, "io": 1}) as n:
        yield n


_uid = itertools.count()


def mkpv(xs, distribution="block", dtype=np.float64):
    xs = np.asarray(xs, dtype=dtype)
    pv = PartitionedVector.create(f"t/c{next(_uid)}", len(xs), dtype=dtype,
                                  distribution=distribution)
    if len(xs):
        pv.set_slice(0, len(xs), xs)
    return pv


def _dists(n):
    """Block, cyclic, and an explicit layout with empty + single-element
    segments, all over 3 localities."""
    explicit = ([0, min(1, n), max(n - 1, 0)] if n else [0, 0, 0])
    return [("block", "block"), ("cyclic", "cyclic"),
            ("explicit", dist_mod.explicit(explicit, [2, 0, 1]))]


# ----------------------------------------------------- distribution geometry
@pytest.mark.parametrize("kind", ["block", "cyclic"])
def test_distribution_mapping_round_trips(kind):
    d = getattr(dist_mod, kind)(23, [0, 1, 2])
    assert d.length == 23 and sum(d.sizes) == 23
    seen = []
    for j in range(d.nsegments):
        seen.extend(d.global_indices(j).tolist())
    assert sorted(seen) == list(range(23))
    for i in (0, 1, 11, 22):
        j, loc = d.segment_of(i)
        assert d.global_indices(j)[loc] == i
    runs = d.locate_range(5, 17)
    got = np.empty(12, dtype=np.int64)
    for j, local, pos in runs:
        got[pos] = d.global_indices(j)[local]
    assert got.tolist() == list(range(5, 17))


def test_explicit_distribution_with_empty_and_single_segments():
    d = dist_mod.explicit([0, 1, 4], [2, 0, 1])
    assert d.length == 5 and d.segment_of(0) == (1, 0)
    assert d.segment_of(4) == (2, 3)
    assert d.global_indices(0).size == 0
    with pytest.raises(ValueError):
        dist_mod.explicit([1, 2], [0])  # len mismatch


# ------------------------------------------------------- creation and access
def test_create_access_and_attach_from_worker(net):
    xs = np.arange(20.0) * 2 - 5
    pv = mkpv(xs)
    assert len(pv) == 20 and pv.nsegments == 3
    assert np.array_equal(pv.to_array(), xs)
    assert pv.get(7) == xs[7] and pv[19] == xs[19]
    pv.set(3, -99.0)
    pv[4] = -100.0
    assert pv[3:6].tolist() == [-99.0, -100.0, xs[5]]
    assert pv[-1] == xs[-1]  # python-sequence negative indexing
    pv[-2] = 123.0
    assert pv.get(18) == 123.0
    with pytest.raises(ValueError, match="module level"):
        pv.fill_with(lambda idx: idx)  # loud, not a pickling traceback
    # a worker locality attaches by name and reads through AGAS
    n, nseg, first = rnet.run_on(1, attach_probe, pv.name).get(timeout=60)
    assert (n, nseg, first) == (20, 3, float(xs[0]))
    # segments really are spread over the localities
    assert sorted(pv.owners()) == [0, 1, 2]


def test_cyclic_layout_interleaves(net):
    xs = np.arange(10, dtype=np.int64)
    pv = mkpv(xs, distribution="cyclic", dtype=np.int64)
    # element i lives in segment i % 3
    assert pv.dist.segment_of(4) == (1, 1)
    assert np.array_equal(pv.to_array(), xs)
    assert pv.slice(2, 9).tolist() == list(range(2, 9))


def test_lambda_bodies_fail_loudly(net):
    pv = mkpv([1.0, 2.0])
    with pytest.raises(ValueError, match="module level"):
        alg.count_if(par, pv, lambda x: True)


# -------------------------------------------- segmented vs the seq oracle
@pytest.mark.parametrize("dname,dist", [("block", "block"),
                                        ("cyclic", "cyclic")])
@settings(max_examples=5, deadline=None)
@given(st.lists(st.integers(-50, 50), min_size=0, max_size=40))
def test_segmented_algorithms_match_seq_oracle(rt, net, dname, dist, xs):
    pv = mkpv(xs, distribution=dist)
    want_fn = [float(aff(x)) for x in xs]
    assert alg.reduce(par, pv, init=5) == float(5 + sum(xs))
    assert alg.transform_reduce(par, pv, sq, init=2) == float(
        2 + sum(sq(x) for x in xs))
    assert alg.count_if(par, pv, is_even) == sum(1 for x in xs if is_even(x))
    assert alg.all_of(par, pv, nonneg) == all(nonneg(x) for x in xs)
    assert alg.any_of(par, pv, is_even) == any(is_even(x) for x in xs)
    t = alg.transform(par, pv, aff)
    assert isinstance(t, PartitionedVector) and t.dist is pv.dist
    assert t.to_array().tolist() == want_fn
    inc = alg.inclusive_scan(par, pv)
    assert inc.to_array().tolist() == [float(v) for v in np.cumsum(xs)]
    exc = alg.exclusive_scan(par, pv, init=7)
    assert exc.to_array().tolist() == (
        [7.0] + [float(7 + v) for v in np.cumsum(xs)[:-1]] if xs else [])
    if xs:
        assert alg.min_element(par, pv) == float(min(xs))
        assert alg.max_element(par, pv) == float(max(xs))
    alg.sort(par, pv)
    assert pv.to_array().tolist() == [float(v) for v in sorted(xs)]
    filled = alg.fill(par, pv, 9)
    assert filled is pv and set(pv.to_array().tolist()) <= {9.0}


@pytest.mark.parametrize("dname,dist", _dists(6))
def test_segmented_on_empty_and_single_element_segments(net, dname, dist):
    xs = [4.0, -2.0, 7.0, 0.0, 3.0, -8.0]
    pv = mkpv(xs, distribution=dist)
    assert alg.reduce(par, pv) == sum(xs)
    assert alg.min_element(par, pv) == min(xs)
    inc = alg.inclusive_scan(par, pv)
    assert inc.to_array().tolist() == list(np.cumsum(xs))
    alg.sort(par, pv)
    assert pv.to_array().tolist() == sorted(xs)


def test_segmented_empty_vector(net):
    pv = mkpv([])
    assert len(pv) == 0 and pv.to_array().size == 0
    assert alg.reduce(par, pv, init=3) == 3
    assert alg.count_if(par, pv, is_even) == 0
    assert alg.all_of(par, pv, is_even) is True  # vacuous
    assert alg.exclusive_scan(par, pv, init=2).to_array().size == 0
    with pytest.raises(ValueError, match="empty"):
        alg.min_element(par, pv)


def test_segmented_two_way_task_policy(net):
    pv = mkpv(np.arange(12.0))
    f = alg.reduce(par_task, pv)
    assert isinstance(f, Future) and f.get(timeout=60) == 66.0
    f2 = alg.inclusive_scan(par_task, pv)
    assert isinstance(f2, Future)
    assert f2.get(timeout=120).to_array().tolist() == list(
        np.cumsum(np.arange(12.0)))


def test_scan_float_carry_over_int_segments_promotes(net):
    pv = mkpv([1, 2, 3, 4, 5, 6], dtype=np.int64)
    exc = alg.exclusive_scan(par, pv, init=0.5)
    want = [0.5, 1.5, 3.5, 6.5, 10.5, 15.5]
    assert exc.to_array().tolist() == want
    # the handle's dtype must reflect the promotion, or slice() truncates
    assert exc.dtype == np.float64
    assert exc.slice(0, 6).tolist() == want


def test_free_releases_segments_and_name(net):
    from repro.core import agas as _agas

    pv = mkpv(np.arange(6.0))
    name, gid0 = pv.name, pv.segment_gid(0)
    # derived result, freed after use (the transient-result hygiene path)
    t = alg.transform(par, pv, aff)
    t_total = float(alg.reduce(par, t))
    t.free()
    with pytest.raises(rnet.UnknownGid):
        rnet.apply_remote(attach_probe, t.segment_gid(1)).get(timeout=60)
    pv.free()
    assert not _agas.default().contains(gid0)
    assert not _agas.default().contains(name)
    # the name is reusable, and attach() does not serve the stale handle
    pv2 = PartitionedVector.create(name, 3)
    assert len(PartitionedVector.attach(name)) == 3
    assert t_total == sum(aff(x) for x in np.arange(6.0))
    pv2.free()


# --------------------------------------------------- work went to the data
def _wire_bytes(net):
    total = 0.0
    for loc in range(net.n_localities):
        snap = rnet.query_counters(loc, "/net{*}/bytes/sent")
        total += sum(v for _k, v in snap)
    return total


def test_for_each_moves_no_element_bytes(net):
    n = 40_000  # 320 KB of float64 elements
    pv = PartitionedVector.create(f"t/bytes{next(_uid)}", n)
    pv.fill_with(_iota)
    element_bytes = n * 8
    before = _wire_bytes(net)
    alg.for_each(par, pv, touch)
    mid = _wire_bytes(net)
    pv.to_array()
    after = _wire_bytes(net)
    d_foreach = mid - before
    d_fetch_all = after - mid
    assert d_fetch_all > 0.6 * element_bytes, "fetch-all must move the data"
    assert d_foreach < element_bytes * 0.05, \
        f"for_each moved {d_foreach} bytes — work did not go to the data"
    assert d_foreach < d_fetch_all / 10


def _iota(idx):
    return idx.astype(np.float64)


# ----------------------------------------------------- placement / rebalance
def test_move_segment_keeps_gid_and_contents(net):
    xs = np.arange(9.0)
    pv = mkpv(xs)
    gid = pv.segment_gid(0)
    pv.move_segment(0, 2)
    assert pv.owner_of(0) == 2 and pv.segment_gid(0) == gid
    assert np.array_equal(pv.to_array(), xs)


def test_rebalance_preserves_contents_under_concurrent_reads(net):
    xs = np.arange(400.0)
    pv = mkpv(xs)
    stop = threading.Event()
    errors = []

    def reader():
        rng = np.random.default_rng(0)
        while not stop.is_set():
            lo = int(rng.integers(0, 360))
            try:
                got = pv.slice(lo, lo + 32)
                if not np.array_equal(got, xs[lo:lo + 32]):
                    errors.append((lo, got))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        assert pv.rebalance([1, 2, 0]) == [1, 2, 0]
        assert pv.rebalance([2, 0, 1]) == [2, 0, 1]
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    assert not errors
    assert pv.owners() == [2, 0, 1]
    assert np.array_equal(pv.to_array(), xs)


# -------------------------------------------------- consumers ride along
def test_sharded_dataset_matches_oracle_and_feeds_locally(net):
    from repro.configs import get_config
    from repro.data.pipeline import (DataConfig, ShardedTokenDataset,
                                     synth_token_rows)

    cfg = get_config("qwen25_3b", smoke=True)
    dcfg = DataConfig(batch_size=4, seq_len=16)
    ds = ShardedTokenDataset.create(f"t/ds{next(_uid)}", cfg, dcfg, rows=30)
    oracle = synth_token_rows(np.arange(30), cfg, dcfg)
    assert np.array_equal(ds.pv.to_array(), oracle)
    feeder = ds.feeder()
    assert feeder.global_rows.shape[0] == 10  # locality 0's block share
    batch = feeder.get(0).get(timeout=60)
    assert batch["tokens"].shape == (4, 17)
    local = {tuple(r) for r in oracle[feeder.global_rows]}
    assert all(tuple(np.asarray(r)) in local for r in batch["tokens"]), \
        "batch rows must come from locally-owned segments"
    # deterministic per step
    again = feeder.get(0).get(timeout=60)
    assert np.array_equal(np.asarray(batch["tokens"]), np.asarray(again["tokens"]))


def test_partitioned_checkpoint_owner_writes_own_shard(net, tmp_path):
    from repro.checkpoint import ckpt

    xs = np.arange(24.0) * 1.5
    pv = mkpv(xs)
    pv.move_segment(0, 1)  # placement at SAVE time must be what restores
    out = ckpt.save_partitioned(tmp_path, step=5, pv=pv)
    import json

    manifest = json.loads((out / "partitioned.json").read_text())
    # each shard was written by the locality owning the segment
    assert [s["locality"] for s in manifest["shards"]] == [1, 1, 2]
    assert (out / "shard_00001.npy").exists()
    step, pv2 = ckpt.restore_partitioned(tmp_path, name=f"t/rst{next(_uid)}")
    assert step == 5
    assert np.array_equal(pv2.to_array(), xs)
    assert pv2.owners() == [1, 1, 2], "save-time placement must survive"
