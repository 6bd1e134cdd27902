"""The readers of the engine's dispatch log (``repro.serving.telemetry``)
on a traced run of the tiny cell, on the CPU: each reads a value, and the
prefill live-row share agrees with a count made from the harness's own
step records."""
import time

import tiny  # sets up the import path
import harness

READERS = ("host_gap_share", "readback_share", "decode_ms_per_tick", "prefill_live_row_share")


def test_readers_of_a_traced_run(monkeypatch):
    seen = []
    view = harness.View
    monkeypatch.setattr(harness, "View", lambda *a, **kw: seen.append(view(*a, **kw)) or seen[-1])
    cell = tiny.cell()
    result, checks, info = harness.measure(cell, 2**31 + 11, 2.5, True, t_start=time.monotonic(),
                                           peak=tiny.PEAK)
    assert result["correct"], checks
    metrics = {k: m["value"] for k, m in result["metrics"].items()}
    assert set(READERS) <= set(metrics), metrics
    assert 0.0 < metrics["readback_share"] < metrics["host_gap_share"] <= 100.0
    assert metrics["decode_ms_per_tick"] > 0.0
    split = info["metric_notes"]["host_gap_share"]
    assert set(split) == {"readback", "drain", "caller", "schedule", "upload", "dispatch",
                          "host_gap_s", "dispatches"}
    seconds, program = info["metric_notes"]["readback_share"]
    assert seconds > 0.0
    assert program in ("decode_step", "prefill_step") or program.startswith("decode_window_")

    # each step that ran prefill chunks dispatched one prefill program of
    # slots x chunk rows, whose live rows are the chunks' lengths
    p = cell.params
    steps = seen[0].steps
    live = sum(n for r in steps for _, _, n in r.prefill)
    programs = sum(1 for r in steps if r.prefill)
    assert programs > 0
    assert info["metric_notes"]["prefill_live_row_share"] == {
        "live_rows": live, "rows": programs * p["slots"] * p["prefill_chunk"]}
    assert metrics["prefill_live_row_share"] == 100.0 * live / (programs * p["slots"] * p["prefill_chunk"])
