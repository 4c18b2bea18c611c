"""``tok_window_drift_pct`` on made-up segment rates, its entry in
BENCHMARK.json, and the window line ``run.py`` prints in rehearsal."""

import json
import os
import types

import pytest

from benchmarks.context import RunContext, window_drift
from benchmarks.layer_metrics import tok_window_drift_pct

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ctx_of(rates):
    return RunContext(
        job=types.SimpleNamespace(kernel_costs={}), chips=1, peak={},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, rates=tuple(rates))


@pytest.mark.parametrize("rates, signed, read", [
    # Flat but for one slow segment in the middle: the thirds' medians agree.
    ([100.0] * 12 + [90.0] + [100.0] * 11, 0.0, 0.0),
    # Falling as the Qwen cell's did at 1e-4: 25,048 at the start, 22,330
    # from the sixteenth segment of 24 on.
    ([25048.0 - 170.0 * i for i in range(16)] + [22330.0] * 8,
     22330.0 / (25048.0 - 170.0 * 3.5) - 1.0,
     100.0 * (1.0 - 22330.0 / (25048.0 - 170.0 * 3.5))),
    # Rising reads the same size, the other sign.
    ([100.0, 100.0, 101.0, 102.0, 103.0, 103.0], 0.03, 3.0),
    # Three segments are three thirds of one.
    ([100.0, 50.0, 98.0], -0.02, 2.0),
    # Fewer than three: nothing to read, and the line leaves the metric out.
    ([100.0, 90.0], None, None),
    ([], None, None),
])
def test_drift_on_made_up_rates(rates, signed, read):
    got = window_drift(tuple(rates))
    value = tok_window_drift_pct.read(ctx_of(rates))
    if signed is None:
        assert got is None and value is None
    else:
        assert got == pytest.approx(signed, abs=1e-12)
        assert value == pytest.approx(read, abs=1e-9) and value >= 0.0


def test_a_context_without_rates_reads_nothing():
    """The field has a default, so a context built as before PR 40 (the
    other readers' tests) still builds, and the reader returns None."""
    ctx = RunContext(
        job=None, chips=1, peak={}, throughput=1.0, spans={},
        first_step_s=1.0, step_compiles=1, memory_peak_bytes=0)
    assert ctx.rates == () and tok_window_drift_pct.read(ctx) is None


def test_the_entry_lists_every_cell_that_reports_tok_s_chip():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "tok_window_drift_pct"]
    tok, = [m for m in bench["end_to_end"] if m["name"] == "tok_s_chip"]
    assert entry == {"name": "tok_window_drift_pct", "unit": "%",
                     "better": "lower", "source": "host_clock",
                     "layer": "Training loop", "moves": "tok_s_chip",
                     "workloads": tok["workloads"]}
