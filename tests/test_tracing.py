"""The streaming job's host spans and byte counters.

Under ``jax.profiler`` on the CPU, every span the job declares lands on the
host plane, each phase of a batch lies inside its ``stream.batch``, and the
phases cover the batch.  ``BatchMetrics.put_bytes`` / ``fetch_bytes`` agree
with the shapes the job moves.  The spans' names are the ones the
benchmark's trace reduction loads (``chipbench/program_spans.py``).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import compat
from repro.core.drm import DRConfig
from repro.core.streaming import SPANS, StreamingJob
from repro.exchange import FaultPlan, FaultyBackend, LaneFault
from repro.exchange.spec import DISTANCE_CLASSES

BENCH = Path(__file__).resolve().parents[1] / "chipbench"
STATE, PARTS, EVENTS = 1 << 12, 8, 1 << 10  # chipbench/tests/test_run.py's sizes
#: spans a one-worker job cannot write: lane removal needs two workers, and
#: on one worker a migration is the identity, so ``dr.migrate`` holds no phase
NEEDS_TWO_WORKERS = {"dr.lane", "dr.migrate.fetch", "dr.migrate.plan", "dr.migrate.start"}


def _load(name: str):
    """A module of the benchmark's, by file (its directory stays off
    ``sys.path``: its module names are generic)."""
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}", BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mesh1() -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


def _zipf_batches(num: int, seed: int = 0) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    ids = rng.choice(1 << 30, 1000, replace=False)
    return [ids[(rng.zipf(1.2, EVENTS) - 1) % 1000].astype(np.int64) for _ in range(num)]


def _job(**dr) -> StreamingJob:
    return StreamingJob(mesh=_mesh1(), num_partitions=PARTS, state_capacity=STATE,
                        dr=DRConfig(**dr))


def _trace(tmp_path, drive) -> list[dict]:
    """Host spans written while ``drive()`` runs under the profiler."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        drive()
    finally:
        jax.profiler.stop_trace()
    spans = _load("program_spans")
    return spans.load_program_spans(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))


def test_spans_cover_every_batch(tmp_path):
    job = _job()
    batches = _zipf_batches(4)
    job.run(batches[:1])  # compiles outside the trace

    def drive():
        job.run(batches[1:])
        jax.block_until_ready(job.state_keys)

    events = _trace(tmp_path, drive)
    assert any(m.repartitioned for m in job.metrics[1:])
    names = {e["name"] for e in events}
    assert set(SPANS) - names == {"dr.resize", "dr.switch", "dr.recover"} | NEEDS_TWO_WORKERS
    iv = lambda e: (e["start_ns"], e["start_ns"] + e["dur_ns"])  # noqa: E731
    batch_spans = sorted(iv(e) for e in events if e["name"] == "stream.batch")
    assert len(batch_spans) == 3
    covered = 0
    top = ("stream.feed", "stream.count_sync", "dr.observe", "dr.decide",
           "stream.drain", "dr.migrate", "stream.account")
    for e in events:
        if e["name"] == "stream.batch":
            continue
        s, t = iv(e)
        inside = [b for b in batch_spans if b[0] <= s and t <= b[1]]
        # the final drain is the harness's read of the state, after the batches
        assert inside or e["name"] == "stream.drain", e
        if inside and e["name"] in top:
            covered += t - s
    # the phases cover the batches (what is left is the gaps between them)
    assert covered >= 0.9 * sum(t - s for s, t in batch_spans)


def test_action_spans(tmp_path):
    """A resize, a backend switch and a recovery from a lost worker each
    write their span."""
    batches = _zipf_batches(6, seed=1)
    resize = _job(imbalance_trigger=1e9)
    switch = StreamingJob(mesh=_mesh1(), num_partitions=4, state_capacity=2048,
                          capacity_factor=4.0,
                          dr=DRConfig(auto_backend=True, backend_patience=2,
                                      backend_cooldown=50, imbalance_trigger=1e9))
    plan = FaultPlan(faults=(LaneFault(2, 0, "kill"),))
    lost = StreamingJob(mesh=_mesh1(), num_partitions=PARTS, state_capacity=STATE,
                        dr=DRConfig(imbalance_trigger=1e9, snapshot_interval=1),
                        exchange_backend=FaultyBackend("dense", plan))

    def drive():
        resize.run(batches[:1])
        resize.resize(2 * PARTS)
        resize.run(batches[1:3])
        switch.run([np.random.default_rng(0).integers(0, 500, 2048) for _ in range(6)])
        lost.run(batches[:4])

    names = {e["name"] for e in _trace(tmp_path, drive)}
    assert any(m.resized for m in resize.metrics)
    assert any(m.action == "switch_backend" for m in switch.metrics)
    assert lost.recoveries
    assert {"dr.resize", "dr.switch", "dr.recover"} <= names


def test_put_and_fetch_bytes_follow_the_shapes():
    job = _job()
    still = _job(imbalance_trigger=1e9)  # the same batches, never repartitioned
    batches = _zipf_batches(3)
    ms, base = job.run(batches), still.run(batches)
    for m in ms:  # int32 keys, float32 values (payload_dim 1), bool valid flags
        assert m.put_bytes == EVENTS * (4 + 4 + 1)
    assert any(m.repartitioned for m in ms)
    assert not any(m.repartitioned for m in base)
    w = job.num_workers
    # a migration fetches no key table: at most the [W, W] route counts,
    # plus control outputs — the pre-action drain's live-row count, the
    # moved, total, shipped and overflow scalars, the shipped rows by
    # distance class, and (folded a batch later) the [W] lane overflow
    control = 4 * (1 + 4 + DISTANCE_CLASSES + w)
    for m, b in zip(ms, base):
        assert 0 < m.fetch_bytes < STATE * 4
        assert m.fetch_bytes - b.fetch_bytes <= 4 * w * w + control


def test_counters_add_no_sync():
    """The byte counters and spans leave the steady state sync-free (the
    same stream as test_overlap's sync-free test)."""
    rng = np.random.default_rng(0)
    batches = [(rng.zipf(1.5, 384) % 200).astype(np.int64) for _ in range(6)]
    job = StreamingJob(num_partitions=8, state_capacity=2048, payload_dim=2,
                       dr=DRConfig(imbalance_trigger=1e9), seed=0)
    job.run(batches[:2])
    compat.reset_host_sync_count()
    before = compat.host_fetch_bytes()
    ms = job.run(batches[2:])
    assert compat.host_sync_count() == 0
    assert compat.host_fetch_bytes() - before == sum(m.fetch_bytes for m in ms)
    # each batch puts its own records (int32 keys, two float32 values,
    # bool valid flags), padded to a multiple of the workers
    w = job.num_workers
    put = [(len(b) + (-len(b)) % w) * (4 + 8 + 1) for b in batches[2:]]
    assert [m.put_bytes for m in ms] == put


def test_host_fetch_counts_bytes_inside_and_outside_safe_points():
    x = jax.numpy.zeros((3, 5), jax.numpy.int32)
    before, syncs = compat.host_fetch_bytes(), compat.host_sync_count()
    with compat.safe_point():
        compat.host_fetch(x)
    compat.host_fetch(x)
    compat.host_fetch(np.zeros(7))  # already on the host: nothing moves
    assert compat.host_fetch_bytes() - before == 2 * 60
    assert compat.host_sync_count() - syncs == 1


def test_every_span_is_loaded_by_the_trace_reduction():
    """A renamed or added span must not drop out of the benchmark's
    breakdown silently."""
    spans = _load("program_spans")
    assert spans.PROGRAM_SPANS == SPANS
    assert len(set(SPANS)) == len(SPANS)


def test_undeclared_span_is_refused():
    from repro.core.streaming import _span

    with pytest.raises(AssertionError):
        _span("stream.nothing")
