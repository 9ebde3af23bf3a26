"""The control of ``correct``: the reference with its per-key state in
bfloat16, fed a cell's stream, must fail the comparison a run uses."""
import control
import reference
import source

TINY = source.Traffic(name="tiny", arrivals="backlog", zipf_exponent=1.2,
                      population=1 << 12, id_range=1 << 30, drift_every_batches=8,
                      drift_fraction=0.3, batch_events_per_chip=1 << 10,
                      prefill="population_sweep")


def test_bf16_control_is_not_correct():
    checks, checked = control.control_checks(TINY, 2**31 + 5, 1, batches=20)
    assert checked > 0
    assert not reference.passed(checks)
    assert checks["keys_wrong"]["value"] > 0 and checks["max_abs_err"]["value"] > 0
