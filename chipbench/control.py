"""The control of the comparison that decides ``correct``.

    python chipbench/control.py --workload wc1-backlog --seeds 11 12 13 --batches 60

The deployment states float32 per-key counts.  The control puts the plain
reference in the program's place, with its keyed state one precision lower,
in bfloat16: on the device, each batch's exact per-key counts are added
into a bfloat16 table.  It is fed the cell's own stream (the prefill sweep
and ``--batches`` batches after it, about as many as a run of the cell
takes) and compared with the exact reference by the same
``reference.compare`` a run uses.  It must come out not correct: the
comparison can tell a count kept in bfloat16 from one kept in float32.

The benchmark's runs never run this; it is kept here, with a test at a small
size in ``tests/test_control.py``, so that the limits of ``reference.py``
stay shown to separate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import reference  # noqa: E402
import source  # noqa: E402


def control_checks(traffic: source.Traffic, seed: int, chips: int, batches: int):
    """The bf16 control over the sweep and ``batches`` more batches of the
    cell's stream (its warm-up and window); returns ``(checks, keys_checked)``."""
    import jax
    import jax.numpy as jnp

    stream = source.Stream(traffic, seed, chips)
    n = traffic.population

    @jax.jit
    def add(acc, idx):
        exact = jnp.zeros(n, jnp.float32).at[idx].add(1.0)
        return (acc.astype(jnp.float32) + exact).astype(jnp.bfloat16)

    acc = jnp.zeros(n, jnp.bfloat16)
    total = stream.sweep_batches + batches
    for b in range(total):
        idx = stream.make(b)
        stream.count(idx)
        acc = add(acc, jnp.asarray(idx, jnp.int32))
    held = np.asarray(acc.astype(jnp.float32), np.float64)
    live = held != 0
    keys = np.where(live, stream.ids, reference.KEY_SENTINEL)[None]
    return reference.compare(keys, held[None, :, None], stream.ids, stream.counts, 0)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--batches", type=int, required=True,
                    help="batches to feed after the prefill sweep")
    args = ap.parse_args(argv)
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    traffic = source.Traffic.load(cell["traffic"])
    import jax

    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    for seed in args.seeds:
        checks, checked = control_checks(traffic, seed, int(cell["chips"]), args.batches)
        print(json.dumps({"seed": seed, "correct": reference.passed(checks),
                          "keys_checked": checked, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
