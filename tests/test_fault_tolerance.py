"""Fault-tolerance tier-1 shard (ISSUE 9): crash-safe ingest, elastic
resize, torn checkpoints, degraded-mode serving.

Pinned recovery contracts (bitwise where the contract is bitwise):

  * kill the ingest worker mid-round -> WAL replay onto a fresh service
    -> ``finalize()`` BITWISE the uninterrupted run;
  * ``reshard_stream`` across 8 -> 4 and 4 -> 8 grids mid-stream ->
    bitwise finalize (8 fake devices, subprocess);
  * the reshard hop's measured ledger bytes equal the
    ``plan.model.stream_reshard_traffic_words`` prediction exactly
    (drift = 0) on the pinned grid pairs;
  * a torn checkpoint is NEVER restored: ``latest_step`` skips it,
    explicit ``restore(step=...)`` raises TornCheckpointError, and
    ``quarantine_torn`` renames it out of the step sequence;
  * ``elastic_restore`` 8 -> 4 fake devices + ``rescale_accum`` (the
    round trip launch/elastic.py's docstring advertises);
  * poison-lane excision: when a round's retries exhaust, only the
    poison lane is quarantined — its cohort's tenants still land;
  * transient-round retry with backoff under a deadline;
  * ``WorkerDied`` fast-fail on submit/flush/close_stream after a worker
    crash (never hang on a queue nobody drains); idempotent shutdown.
  * distributed rounds are exactly-once per lane: a per-lane dispatch
    that fails partway through never re-applies its landed prefix on
    retry or in the poison-excision fallback;
  * WAL replay onto a distributed service applies records additively
    (full-shape, no row offset) and refuses local-mode row slabs;
    replaying a reopened WriteAheadLog advances its applied watermark so
    a reattached queue can resolve and truncate the recovered prefix.
"""
import os
import threading
import time

import numpy as np
import pytest

from dist_helper import run_distributed

from repro.checkpoint import ckpt
from repro.stream import faults
from repro.stream import wal as wal_mod
from repro.stream.ingest import IngestQueue, WorkerDied
from repro.stream.service import SketchService
from repro.stream.state import StreamConfig


@pytest.fixture(autouse=True)
def _clean_faults():
    """The chaos registry is process-global: guarantee every test starts
    and ends with nothing armed."""
    faults.clear()
    yield
    faults.clear()


def _mk_traffic(rng, streams, updates, n1, n2):
    """updates-per-stream row-block traffic, per-stream FIFO order."""
    traffic = []
    for _ in range(updates):
        for s in range(streams):
            k = int(rng.integers(1, 17))
            traffic.append((s, rng.standard_normal((k, n2)).astype("float32"),
                            int(rng.integers(0, n1 - k + 1))))
    return traffic


def _reference(cfgs, traffic):
    """The run that never crashes: same traffic, same per-stream order."""
    ref = SketchService()
    sids = [ref.open(c) for c in cfgs]
    for s, H, row0 in traffic:
        ref.update(sids[s], H, row0=row0)
    return [np.asarray(ref.sketch(s)) for s in sids]


# ---------------------------------------------------------------------------
# write-ahead log
# ---------------------------------------------------------------------------


def test_wal_append_scan_roundtrip(tmp_path):
    path = str(tmp_path / "ingest.wal")
    rng = np.random.default_rng(0)
    payloads = [(s, int(rng.integers(0, 8)),
                 rng.standard_normal((1 + s, 6)).astype("float32"))
                for s in range(5)]
    with wal_mod.WriteAheadLog(path) as wal:
        seqs = [wal.append(sid, row0, H) for sid, row0, H in payloads]
        assert seqs == [1, 2, 3, 4, 5]
        assert wal.depth == 5

        records, torn = wal_mod.scan(path)
        assert torn is None
        for rec, (sid, row0, H) in zip(records, payloads):
            assert (rec.sid, rec.row0) == (sid, row0)
            assert rec.words == H.size
            np.testing.assert_array_equal(rec.H, H)   # bitwise payload

        # watermark advance + truncate drop the applied prefix atomically
        wal.mark_applied(3)
        assert wal.watermark == 3 and wal.depth == 2
        assert wal.truncate() == 2
        assert [r.seqno for r in wal.pending()] == [4, 5]

    # reopen resumes the seqno sequence past what is durable
    with wal_mod.WriteAheadLog(path) as wal2:
        assert wal2.append(9, 0, payloads[0][2]) == 6


def test_wal_torn_tail_discarded(tmp_path):
    path = str(tmp_path / "ingest.wal")
    H = np.arange(12, dtype=np.float32).reshape(3, 4)
    with wal_mod.WriteAheadLog(path) as wal:
        for _ in range(3):
            wal.append(1, 0, H)
    # crash mid-append: cut into the last record's payload/CRC
    size = os.path.getsize(path)
    with open(path, "rb+") as f:
        f.truncate(size - 7)
    records, torn = wal_mod.scan(path)
    assert len(records) == 2 and torn is not None
    assert "truncated" in torn.reason
    # reopening repairs the file to its intact prefix and resumes seqnos
    with wal_mod.WriteAheadLog(path) as wal2:
        assert wal2.append(1, 0, H) == 3
    records, torn = wal_mod.scan(path)
    assert torn is None and [r.seqno for r in records] == [1, 2, 3]


def test_wal_bad_magic_is_torn(tmp_path):
    path = str(tmp_path / "ingest.wal")
    with open(path, "wb") as f:
        f.write(b"NOTAWALRECORD???")
    records, torn = wal_mod.scan(path)
    assert records == [] and torn.reason == "bad magic" and torn.offset == 0


def test_kill_worker_mid_round_wal_replay_bitwise(tmp_path):
    """Acceptance (a): crash the worker mid-round, replay the journal into
    a fresh service — finalize is bitwise the uninterrupted run."""
    rng = np.random.default_rng(1)
    n1, n2, r, streams, updates = 64, 32, 4, 4, 3
    cfgs = [StreamConfig(n1=n1, n2=n2, r=r, seed=s, corange=False)
            for s in range(streams)]
    traffic = _mk_traffic(rng, streams, updates, n1, n2)
    ref_Y = _reference(cfgs, traffic)

    svc = SketchService()
    sids = [svc.open(c) for c in cfgs]
    wal = wal_mod.WriteAheadLog(str(tmp_path / "ingest.wal"))
    q = IngestQueue(svc, wal=wal)
    # every submit of one sid lands in a distinct round, so >= `updates`
    # rounds run — round index updates-1 is mid-stream and guaranteed
    faults.arm("ingest.apply_round", exc=faults.WorkerKilled, times=None,
               match={"round_index": max(2, updates - 1)})
    died = False
    for s, H, row0 in traffic:
        try:
            q.submit(sids[s], H, row0)
        except WorkerDied:
            died = True
            break
    if not died:
        with pytest.raises(WorkerDied):
            q.flush()
        died = True
    faults.disarm("ingest.apply_round")
    assert died and wal.depth > 0     # journaled-but-unapplied tail exists
    q.shutdown()
    q.shutdown()                      # idempotent on a corpse
    wal.close()

    svc2 = SketchService()
    sids2 = [svc2.open(c) for c in cfgs]
    nrec, words = wal_mod.replay(wal.path, svc2,
                                 sid_map=dict(zip(sids, sids2)))
    assert nrec == len(traffic) and words == sum(H.size
                                                 for _, H, _ in traffic)
    for s2, ref in zip(sids2, ref_Y):
        np.testing.assert_array_equal(np.asarray(svc2.sketch(s2)), ref)


def test_wal_replay_respects_watermark(tmp_path):
    """Checkpoint + journal-tail recovery: records at or below the
    restored watermark are skipped, the tail replays bitwise."""
    rng = np.random.default_rng(2)
    cfg = StreamConfig(n1=64, n2=32, r=4, seed=7, corange=False)
    traffic = _mk_traffic(rng, 1, 4, cfg.n1, cfg.n2)
    ref_Y = _reference([cfg], traffic)[0]

    wal = wal_mod.WriteAheadLog(str(tmp_path / "ingest.wal"))
    for _, H, row0 in traffic:
        wal.append(0, row0, H)
    wal.close()

    svc = SketchService()
    sid = svc.open(cfg)
    for _, H, row0 in traffic[:2]:    # "restored from a step-2 checkpoint"
        svc.update(sid, H, row0=row0)
    nrec, _ = wal_mod.replay(wal.path, svc, sid_map={0: sid}, watermark=2)
    assert nrec == len(traffic) - 2
    np.testing.assert_array_equal(np.asarray(svc.sketch(sid)), ref_Y)


# ---------------------------------------------------------------------------
# torn checkpoints
# ---------------------------------------------------------------------------


def test_torn_checkpoint_quarantined_never_restored(tmp_path):
    """Acceptance (c): a torn step is skipped by latest_step, refused by
    explicit restore, and renamed out of the sequence by quarantine."""
    d = str(tmp_path / "ckpt")
    state1 = {"w": np.arange(12, dtype=np.float32).reshape(3, 4)}
    state2 = {"w": state1["w"] + 1.0}
    ckpt.save(d, 1, state1)

    def tear(tmp, **_):
        os.remove(os.path.join(tmp, "manifest.json"))

    faults.arm("ckpt.pre_commit", handler=tear, match={"step": 2})
    ckpt.save(d, 2, state2)           # publishes a torn step_00000002
    faults.disarm("ckpt.pre_commit")

    assert ckpt.torn_steps(d) == [2]
    assert ckpt.latest_step(d) == 1   # torn step skipped, not loaded
    tree, step, _ = ckpt.restore(d, state1)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["w"]), state1["w"])
    with pytest.raises(ckpt.TornCheckpointError):
        ckpt.restore(d, state2, step=2)
    assert ckpt.quarantine_torn(d) == [2]
    assert ckpt.torn_steps(d) == []
    assert os.path.isdir(os.path.join(d, "step_00000002.torn"))


def test_ckpt_crash_before_commit_leaves_no_step(tmp_path):
    """Atomicity: a crash before the os.replace publishes NOTHING — no
    step dir, no tmp leftover visible as a step."""
    d = str(tmp_path / "ckpt")
    state = {"w": np.zeros(3, np.float32)}
    ckpt.save(d, 1, state)
    faults.arm("ckpt.pre_commit", exc=faults.FaultInjected,
               match={"step": 2})
    with pytest.raises(faults.FaultInjected):
        ckpt.save(d, 2, state)
    faults.disarm("ckpt.pre_commit")
    assert ckpt.latest_step(d) == 1
    assert ckpt.torn_steps(d) == []
    assert not os.path.exists(os.path.join(d, "step_00000002"))


# ---------------------------------------------------------------------------
# live mesh resize (8 fake devices, subprocess)
# ---------------------------------------------------------------------------


def test_reshard_stream_8_4_8_bitwise_finalize():
    """Acceptance (b): shrink 8 -> 4 mid-stream, grow 4 -> 8, keep
    updating — finalize is bitwise the never-resized run."""
    run_distributed(r"""
import numpy as np, jax
from repro.core.sketch import make_grid_mesh
from repro.stream import ShardedStreamingSketch, StreamConfig
from repro.stream.elastic import reshard_stream

cfg = StreamConfig(n1=256, n2=128, r=8, seed=11, corange=False)
rng = np.random.default_rng(0)
slabs = [(i * 64, rng.standard_normal((64, 128)).astype("float32"))
         for i in range(4)]

ref = ShardedStreamingSketch(cfg, make_grid_mesh(8, 1, 1), backend="jnp")
for row0, H in slabs:
    ref.update_rows(row0, H)

sk = ShardedStreamingSketch(cfg, make_grid_mesh(8, 1, 1), backend="jnp")
for row0, H in slabs[:2]:
    sk.update_rows(row0, H)
sk = reshard_stream(sk, (4, 1, 1))      # device loss: 8 -> 4
assert tuple(int(sk.mesh.shape[a]) for a in sk.axes) == (4, 1, 1)
sk.update_rows(*slabs[2])               # keep streaming on the small grid
sk = reshard_stream(sk, (8, 1, 1))      # devices came back: 4 -> 8
sk.update_rows(*slabs[3])
assert sk.num_updates == ref.num_updates
np.testing.assert_array_equal(np.asarray(jax.device_get(sk.Y)),
                              np.asarray(jax.device_get(ref.Y)))
print("OK")
""")


def test_reshard_ledger_drift_is_zero():
    """Acceptance (d): the reshard hop's measured HLO bytes equal the
    planner's stream_reshard_traffic_words prediction EXACTLY on the
    pinned pairs — a relayout that moves full new shards, a
    coinciding-layout relabel that moves nothing, and a both-axes
    re-split ((4,1,2) -> (2,1,4): the column axis re-splits while
    already split) that pays TWO full-shard hops (all-to-all +
    collective-permute) — the pair the old model underpriced 2x."""
    run_distributed(r"""
import numpy as np
from repro.core.sketch import make_grid_mesh
from repro.obs import install_ledger
from repro.plan import model as M
from repro.stream import ShardedStreamingSketch, StreamConfig
from repro.stream.elastic import LEDGER_SITE, reshard_stream

cfg = StreamConfig(n1=256, n2=128, r=8, seed=0, corange=False)
rng = np.random.default_rng(0)
H = rng.standard_normal((64, 128)).astype("float32")
# (8,1,1)->(2,2,2): layouts differ -> one full NEW shard per device;
# (8,1,1)->(4,2,1): Y's layout coincides device-for-device -> zero words;
# (4,1,2)->(2,1,4): both Y axes re-split with p3>1 either side -> 2x shard
for old_grid, new_grid, want_pred, want_floor in (
        ((8, 1, 1), (2, 2, 2), 256.0, 128.0),
        ((8, 1, 1), (4, 2, 1), 0.0, 0.0),
        ((4, 1, 2), (2, 1, 4), 512.0, 256.0)):
    led = install_ledger()
    sk = ShardedStreamingSketch(cfg, make_grid_mesh(*old_grid),
                                backend="jnp")
    sk.update_rows(0, H)
    reshard_stream(sk, new_grid)
    pred = M.stream_reshard_traffic_words(cfg.n1, cfg.r, old_grid,
                                          new_grid)
    floor = M.stream_reshard_words(cfg.n1, cfg.r, old_grid, new_grid)
    assert (pred, floor) == (want_pred, want_floor), (pred, floor)
    site = led.site(LEDGER_SITE)
    assert site is not None and site.calls == 1
    assert site.predicted_words == pred
    assert site.lower_bound_words == floor
    assert site.measured_words_per_call == pred, (
        old_grid, new_grid, site.measured_words_per_call, pred)
    assert site.drift == 0.0, (old_grid, new_grid, site.drift)
    print("DRIFT_OK", old_grid, new_grid, site.measured_words_per_call)
print("OK")
""")


def test_service_reshard_and_drain_resume():
    """The degraded-mode arc through the queue: drain -> reshard every
    resident stream -> resume ingest, bitwise against an undisturbed
    distributed service.  (1,1,1) -> (1,1,1) runs the full production
    path — drain, per-stream hop, executable-cache drop, resume — on the
    single-device pytest process."""
    from repro.core.sketch import make_grid_mesh
    from repro.stream.elastic import drain_reshard_resume

    rng = np.random.default_rng(3)
    cfgs = [StreamConfig(n1=32, n2=16, r=4, seed=s, corange=False)
            for s in range(2)]
    traffic = [(s, rng.standard_normal((32, 16)).astype("float32"))
               for _ in range(3) for s in range(2)]

    ref = SketchService(mesh=make_grid_mesh(1, 1, 1))
    ref_sids = [ref.open(c) for c in cfgs]
    for s, H in traffic:
        ref.update(ref_sids[s], H)

    svc = SketchService(mesh=make_grid_mesh(1, 1, 1))
    sids = [svc.open(c) for c in cfgs]
    with IngestQueue(svc) as q:
        for s, H in traffic[:2]:
            q.submit(sids[s], H)
        out = drain_reshard_resume(q, (1, 1, 1))
        assert out == {"drained": 2, "resharded": 2}
        for s, H in traffic[2:]:      # resume: rounds recompile, then land
            q.submit(sids[s], H)
        q.flush(raise_errors=True)
    for sid, ref_sid in zip(sids, ref_sids):
        np.testing.assert_array_equal(np.asarray(svc.sketch(sid)),
                                      np.asarray(ref.sketch(ref_sid)))


def test_elastic_restore_8_to_4_round_trip():
    """The round trip launch/elastic.py's docstring advertises: restore
    one checkpoint onto 8 then 4 fake devices (params bitwise equal), and
    rescale gradient accumulation so the global batch is preserved."""
    run_distributed(r"""
import jax
import numpy as np
import tempfile
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.models import get_api
from repro.train.step import init_state
from repro.checkpoint import ckpt
from repro.launch.elastic import elastic_restore, remesh, rescale_accum

cfg = get_config("llama3-8b").reduced(n_layers=2, d_model=64, d_ff=128,
                                      vocab=128, head_dim=16)
api = get_api(cfg)
state = init_state(api, cfg, RunConfig(steps=10), jax.random.key(0))
d = tempfile.mkdtemp()
ckpt.save(d, 7, state)

mesh8 = remesh(jax.devices(), dp=4, tp=2)
st8, step8, _ = elastic_restore(d, state, mesh=mesh8)
mesh4 = remesh(jax.devices()[:4], dp=2, tp=2)   # half the devices died
st4, step4, _ = elastic_restore(d, state, mesh=mesh4)
assert step8 == step4 == 7
for a, b in zip(jax.tree_util.tree_leaves(st8.params),
                jax.tree_util.tree_leaves(st4.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

accum8, gb8 = rescale_accum(global_batch=128, per_device_batch=4, dp_size=4)
accum4, gb4 = rescale_accum(global_batch=128, per_device_batch=4, dp_size=2)
assert gb8 == gb4 == 128 and accum4 == 2 * accum8
print("OK")
""")


# ---------------------------------------------------------------------------
# degraded-mode ingest: retry, backoff, poison excision, fast-fail
# ---------------------------------------------------------------------------


def test_transient_round_failure_retried_then_lands():
    rng = np.random.default_rng(4)
    cfgs = [StreamConfig(n1=32, n2=16, r=4, seed=s, corange=False)
            for s in range(2)]
    traffic = _mk_traffic(rng, 2, 2, 32, 16)
    ref_Y = _reference(cfgs, traffic)

    svc = SketchService()
    sids = [svc.open(c) for c in cfgs]
    faults.arm("ingest.apply_round", exc=faults.FaultInjected, times=1)
    with IngestQueue(svc, max_retries=2, backoff_base=0.0) as q:
        for s, H, row0 in traffic:
            q.submit(sids[s], H, row0)
        q.flush(raise_errors=True)    # the retry absorbed the fault
        st = q.stats()
    assert st["retries"] >= 1 and st["errors"] == 0
    assert st["quarantined"] == 0 and st["applied"] == len(traffic)
    assert faults.fire_count("ingest.apply_round") == 1
    for sid, ref in zip(sids, ref_Y):
        np.testing.assert_array_equal(np.asarray(svc.sketch(sid)), ref)


def test_retry_deadline_forfeits_remaining_retries():
    svc = SketchService()
    sid = svc.open(StreamConfig(n1=32, n2=16, r=4, seed=0, corange=False))
    H = np.ones((4, 16), np.float32)
    # the round ALWAYS fails; with a 10ms budget and 0.2s backoff the
    # worker must give up after one retry and fall back per-lane (the
    # lane itself is healthy, so the update still lands)
    faults.arm("ingest.apply_round", exc=faults.FaultInjected, times=None)
    with IngestQueue(svc, max_retries=5, backoff_base=0.2,
                     retry_deadline=0.01) as q:
        q.submit(sid, H, 0)
        q.flush(raise_errors=True)
        st = q.stats()
    assert st["applied"] == 1 and st["errors"] == 0
    assert st["retries"] < 5          # deadline forfeited the rest


def test_poison_lane_excised_cohort_survives():
    rng = np.random.default_rng(5)
    cfgs = [StreamConfig(n1=32, n2=16, r=4, seed=s, corange=False)
            for s in range(3)]
    traffic = _mk_traffic(rng, 3, 2, 32, 16)
    ref_Y = _reference(cfgs, traffic)

    svc = SketchService()
    sids = [svc.open(c) for c in cfgs]
    bad = sids[1]
    # every fused round fails -> per-lane fallback; exactly one tenant is
    # poison, the others must land every time
    faults.arm("ingest.apply_round", exc=faults.FaultInjected, times=None)
    faults.arm("ingest.apply_lane", exc=faults.FaultInjected, times=None,
               match={"sid": bad})
    with IngestQueue(svc, max_retries=0, backoff_base=0.0) as q:
        for s, H, row0 in traffic:
            q.submit(sids[s], H, row0)
        applied = q.flush()
        st = q.stats()
        with pytest.raises(RuntimeError, match=r"ingest failure"):
            q.flush(raise_errors=True)
    assert applied == 4 and st["quarantined"] == 2 and st["errors"] == 2
    # healthy tenants: bitwise identical to the undisturbed run
    for sid, ref in zip(sids, ref_Y):
        if sid != bad:
            np.testing.assert_array_equal(np.asarray(svc.sketch(sid)), ref)
    # the poison lane was excised BEFORE it could touch its accumulators
    fresh = SketchService()
    fsid = fresh.open(cfgs[1])
    np.testing.assert_array_equal(np.asarray(svc.sketch(bad)),
                                  np.asarray(fresh.sketch(fsid)))


def test_distributed_partial_round_retry_exactly_once():
    """A distributed round applies lanes sequentially; when lane k fails
    mid-round, the retry must re-run ONLY the not-yet-applied suffix —
    the landed prefix must not double-apply into (Y, W)."""
    from repro.core.sketch import make_grid_mesh

    rng = np.random.default_rng(6)
    cfgs = [StreamConfig(n1=32, n2=16, r=4, seed=s, corange=False)
            for s in range(3)]
    deltas = [rng.standard_normal((32, 16)).astype("float32")
              for _ in range(3)]

    ref = SketchService(mesh=make_grid_mesh(1, 1, 1))
    ref_sids = [ref.open(c) for c in cfgs]
    for rs, H in zip(ref_sids, deltas):
        ref.update(rs, H)

    svc = SketchService(mesh=make_grid_mesh(1, 1, 1))
    sids = [svc.open(c) for c in cfgs]
    # middle lane fails ONCE: attempt 1 lands lane 0 then dies; the retry
    # must start at lane 1, not lane 0
    faults.arm("ingest.dispatch_lane", exc=faults.FaultInjected, times=1,
               match={"sid": sids[1]})
    with IngestQueue(svc, max_retries=2, backoff_base=0.0) as q:
        q.hold()                      # one batch -> one 3-lane round
        for sid, H in zip(sids, deltas):
            q.submit(sid, H)
        q.release()
        q.flush(raise_errors=True)
        st = q.stats()
    assert st["retries"] == 1 and st["quarantined"] == 0
    assert st["applied"] == 3 and st["errors"] == 0
    for sid, rs in zip(sids, ref_sids):
        np.testing.assert_array_equal(np.asarray(svc.sketch(sid)),
                                      np.asarray(ref.sketch(rs)))


def test_distributed_poison_lane_excised_exactly_once():
    """Retries exhaust on a persistently-poison lane mid-round: the
    fallback excises only that lane, and the lanes that landed before the
    first failure are NOT re-applied by the fallback."""
    from repro.core.sketch import make_grid_mesh

    rng = np.random.default_rng(7)
    cfgs = [StreamConfig(n1=32, n2=16, r=4, seed=s, corange=False)
            for s in range(3)]
    deltas = [rng.standard_normal((32, 16)).astype("float32")
              for _ in range(3)]

    ref = SketchService(mesh=make_grid_mesh(1, 1, 1))
    ref_sids = [ref.open(c) for c in cfgs]
    for rs, H in zip(ref_sids, deltas):
        ref.update(rs, H)

    svc = SketchService(mesh=make_grid_mesh(1, 1, 1))
    sids = [svc.open(c) for c in cfgs]
    bad = sids[1]
    faults.arm("ingest.dispatch_lane", exc=faults.FaultInjected,
               times=None, match={"sid": bad})
    faults.arm("ingest.apply_lane", exc=faults.FaultInjected,
               times=None, match={"sid": bad})
    with IngestQueue(svc, max_retries=1, backoff_base=0.0) as q:
        q.hold()
        for sid, H in zip(sids, deltas):
            q.submit(sid, H)
        q.release()
        applied = q.flush()
        st = q.stats()
    assert applied == 2 and st["quarantined"] == 1 and st["errors"] == 1
    # healthy lanes land exactly once — bitwise vs the undisturbed run
    for sid, rs in zip(sids, ref_sids):
        if sid != bad:
            np.testing.assert_array_equal(np.asarray(svc.sketch(sid)),
                                          np.asarray(ref.sketch(rs)))
    # the poison lane never touched its accumulators
    fresh = SketchService(mesh=make_grid_mesh(1, 1, 1))
    fsid = fresh.open(cfgs[1])
    np.testing.assert_array_equal(np.asarray(svc.sketch(bad)),
                                  np.asarray(fresh.sketch(fsid)))


def test_submit_rejects_row0_on_mesh():
    """A row-block submit against a distributed service is rejected at
    submit time with service.update's semantics — never silently applied
    as an additive delta at row 0."""
    from repro.core.sketch import make_grid_mesh

    svc = SketchService(mesh=make_grid_mesh(1, 1, 1))
    sid = svc.open(StreamConfig(n1=32, n2=16, r=4, seed=0, corange=False))
    with IngestQueue(svc) as q:
        with pytest.raises(ValueError, match="row0"):
            q.submit(sid, np.ones((4, 16), np.float32), 3)
        q.submit(sid, np.ones((32, 16), np.float32))   # row0=0 flows
        q.flush(raise_errors=True)
        st = q.stats()
    assert st["rejected"] == 1 and st["applied"] == 1


def test_wal_replay_distributed_additive_and_watermark(tmp_path):
    """Replay onto a distributed service: records apply as full-shape
    additive updates (row0 dropped, as live distributed ingest would),
    bitwise; the reopened journal's watermark advances so the recovered
    prefix resolves; a journaled local-mode row slab is refused."""
    from repro.core.sketch import make_grid_mesh

    rng = np.random.default_rng(8)
    cfg = StreamConfig(n1=32, n2=16, r=4, seed=9, corange=False)
    deltas = [rng.standard_normal((32, 16)).astype("float32")
              for _ in range(3)]

    ref = SketchService(mesh=make_grid_mesh(1, 1, 1))
    rsid = ref.open(cfg)
    for H in deltas:
        ref.update(rsid, H)

    path = str(tmp_path / "ingest.wal")
    with wal_mod.WriteAheadLog(path) as wal:
        for H in deltas:
            wal.append(0, 0, H)
    # crash + reopen: the watermark restarts at 0, every record pending
    wal2 = wal_mod.WriteAheadLog(path)
    assert wal2.depth == 3
    svc = SketchService(mesh=make_grid_mesh(1, 1, 1))
    sid = svc.open(cfg)
    nrec, words = wal_mod.replay(wal2, svc, sid_map={0: sid})
    assert nrec == 3 and words == sum(H.size for H in deltas)
    assert wal2.watermark == 3 and wal2.depth == 0
    assert wal2.truncate() == 0       # replayed prefix is droppable
    np.testing.assert_array_equal(np.asarray(svc.sketch(sid)),
                                  np.asarray(ref.sketch(rsid)))
    # a row slab journaled by a LOCAL service cannot be misapplied here
    wal2.append(0, 5, rng.standard_normal((4, 16)).astype("float32"))
    with pytest.raises(ValueError, match="row0"):
        wal_mod.replay(wal2, svc, sid_map={0: sid})
    wal2.close()


def test_wal_reopen_replay_restores_watermark_for_new_queue(tmp_path):
    """After crash recovery, a NEW IngestQueue attached to the replayed
    journal must be able to advance the watermark past the pre-crash
    seqnos: new submits resolve, truncate drops everything, depth
    returns to 0 (no unbounded journal growth)."""
    rng = np.random.default_rng(9)
    cfg = StreamConfig(n1=64, n2=32, r=4, seed=3, corange=False)
    traffic = _mk_traffic(rng, 1, 4, cfg.n1, cfg.n2)
    ref_Y = _reference([cfg], traffic)[0]

    path = str(tmp_path / "ingest.wal")
    with wal_mod.WriteAheadLog(path) as wal:
        for _, H, row0 in traffic[:3]:      # pre-crash: journaled, unapplied
            wal.append(0, row0, H)

    wal2 = wal_mod.WriteAheadLog(path)      # recovery: reopen + replay
    svc = SketchService()
    sid = svc.open(cfg)
    nrec, _ = wal_mod.replay(wal2, svc, sid_map={0: sid})
    assert nrec == 3
    assert wal2.watermark == 3 and wal2.depth == 0
    with IngestQueue(svc, wal=wal2, wal_truncate_every=1) as q:
        _, H, row0 = traffic[3]
        assert q.submit(sid, H, row0) == 4  # seqnos resume past the prefix
        q.flush(raise_errors=True)
    assert wal2.depth == 0                  # watermark caught up
    assert wal2.truncate() == 0             # journal fully droppable
    wal2.close()
    np.testing.assert_array_equal(np.asarray(svc.sketch(sid)), ref_Y)


def test_submit_blocked_on_full_queue_fails_fast_on_worker_death():
    """The fast-fail contract has to hold for a producer ALREADY blocked
    on a full queue: the worker dying cannot wake queue.Queue.put, so
    submit must poll liveness between short waits and raise WorkerDied
    instead of hanging forever."""
    svc = SketchService()
    sid = svc.open(StreamConfig(n1=32, n2=16, r=4, seed=0, corange=False))
    H = np.ones((4, 16), np.float32)
    entered, block = threading.Event(), threading.Event()

    def killer(**ctx):
        entered.set()
        block.wait(timeout=30.0)
        raise faults.WorkerKilled("chaos: worker dies with the queue full")

    faults.arm("ingest.apply_round", handler=killer, times=None)
    q = IngestQueue(svc, depth=1)
    q.submit(sid, H, 0)                  # worker takes it, parks in killer
    assert entered.wait(30.0)
    q.submit(sid, H, 0)                  # refills the depth-1 queue
    result = {}

    def blocked_submit():
        try:
            q.submit(sid, H, 0)          # full queue: blocks (backpressure)
            result["exc"] = None
        except BaseException as e:
            result["exc"] = e

    t = threading.Thread(target=blocked_submit)
    t.start()
    time.sleep(0.2)
    assert t.is_alive()                  # genuinely blocked, not failed
    block.set()                          # the worker now dies mid-round
    t.join(30.0)
    assert not t.is_alive()
    assert isinstance(result["exc"], WorkerDied)
    q.shutdown()


def test_worker_died_fast_fail_and_idempotent_shutdown():
    svc = SketchService()
    sid = svc.open(StreamConfig(n1=32, n2=16, r=4, seed=0, corange=False))
    H = np.ones((4, 16), np.float32)
    faults.arm("ingest.apply_round", exc=faults.WorkerKilled, times=None)
    q = IngestQueue(svc)
    q.submit(sid, H, 0)
    deadline = time.monotonic() + 30.0
    while q.worker_alive and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not q.worker_alive
    # every entry point fails FAST with the original traceback attached
    with pytest.raises(WorkerDied) as ei:
        q.submit(sid, H, 0)
    assert "WorkerKilled" in ei.value.traceback_text
    with pytest.raises(WorkerDied):
        q.flush()
    with pytest.raises(WorkerDied):
        q.close_stream(sid)
    assert q.heartbeat_age() >= 0.0
    assert q.stats()["worker_alive"] is False
    q.shutdown()
    q.shutdown()                      # joining a corpse is a no-op


# ---------------------------------------------------------------------------
# chaos driver scenarios (the launch/serve.py --chaos drills)
# ---------------------------------------------------------------------------


def test_chaos_shrink_restore_in_process():
    """The shrink-restore drill reshards on the calling process's own
    devices (8 -> 4 -> 8 fake devices here, 4 -> 2 -> 4 on a 2x2 host)
    and spawns nothing: its finalize is bitwise the never-resized run."""
    out = run_distributed(r"""
from repro.stream import faults
res = faults.run_chaos_scenario("shrink-restore", verbose=False)
assert res["recovered"] and res["devices"] == 8, res
print("OK")
""")
    assert "OK" in out


def test_chaos_shrink_restore_not_run_on_one_device():
    """On one device (one CPU device, one chip) the drill cannot shrink:
    it reports itself not run, and ``--chaos all`` stays green."""
    import argparse

    import jax

    from repro.launch.serve import run_chaos
    assert len(jax.devices()) == 1
    res = faults.run_chaos_scenario("shrink-restore", verbose=False)
    assert res["recovered"] is None and "even device count" in res["skipped"]
    out = run_chaos(argparse.Namespace(chaos="shrink-restore", streams=2,
                                       updates=1))
    assert out["shrink-restore"]["skipped"]


@pytest.mark.parametrize("scenario", ["torn-write", "eviction-storm"])
def test_chaos_scenarios_recover(scenario, tmp_path):
    out = faults.run_chaos_scenario(scenario, n1=64, n2=32, r=4, streams=3,
                                    updates=2, workdir=str(tmp_path),
                                    verbose=False)
    assert out["recovered"], out
