"""A run end to end at a tiny size on the CPU, past the harness's look for a
chip: sound runs come out correct, and a run whose timed path is broken
underneath comes out not correct, once for each fault the cell can have (it
runs on one chip, so no exchange between chips can be left out)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import run as harness
import source

ROOT = Path(__file__).resolve().parents[2]
TINY = source.Traffic(name="tiny", arrivals="backlog", zipf_exponent=1.2,
                      population=1000, id_range=1 << 30, drift_every_batches=2,
                      drift_fraction=0.3, batch_events_per_chip=1 << 10,
                      prefill="population_sweep")


def tiny_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "chipbench/configs/wordcount-zipf1.2-1chip.json").read_text())
    config["job"].update(state_capacity=1 << 12, num_partitions=8)
    return harness.Cell("wc1-backlog", 1, config, TINY, bench["end_to_end"], bench["per_layer"])


def run_tiny(seed=2**31 + 77):
    return harness.run_cell(tiny_cell(), seed, 1.0, False, log=lambda s: None)


def test_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "wc1-backlog",
                        "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert "{" not in p.stdout and "TPU" in p.stderr


def test_sound_run_is_correct():
    run, checks = run_tiny()
    assert harness.reference.passed(checks), checks
    assert run.window_batches > 0 and any(m.repartitioned for m in run.window)
    assert run.heavy_slots > 0 and run.hosts > 0
    line = harness.result_line(run, checks, False, [SimpleDevice()])
    assert line["correct"] and list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"events_per_s", "setup_s"}


class SimpleDevice:
    platform, device_kind = "cpu", "cpu"


@pytest.fixture
def broken(monkeypatch):
    """Break the timed path underneath the harness, by name."""
    from repro.core import streaming

    def apply(name):
        calls = {"n": 0}
        orig = streaming.StreamingJob.process_batch
        window_start = TINY.sweep_batches(1) + tiny_cell().config["warmup_batches"]

        def process_batch(self, keys, values=None):
            calls["n"] += 1
            in_window = calls["n"] > window_start
            if name == "state_unchanged" and in_window and calls["n"] % 2:
                return self.metrics[-1]  # the step leaves the state as it was
            if name == "half_batch" and in_window:
                keys = keys.copy()
                keys[len(keys) // 2:] = source.KEY_SENTINEL  # half the events left out
            return orig(self, keys, values)

        monkeypatch.setattr(streaming.StreamingJob, "process_batch", process_batch)
        if name == "altered_answer":
            merge = streaming.merge_into

            def altered(*args, **kw):
                k, v, o = merge(*args, **kw)
                return k, v.at[0, 0].add(1.0), o

            monkeypatch.setattr(streaming, "merge_into", altered)

    return apply


@pytest.mark.parametrize("name", ["state_unchanged", "half_batch", "altered_answer"])
def test_broken_run_is_not_correct(broken, name):
    broken(name)
    _, checks = run_tiny(seed=2**31 + 78)
    assert not harness.reference.passed(checks), (name, checks)


def test_peaks_unknown_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")
