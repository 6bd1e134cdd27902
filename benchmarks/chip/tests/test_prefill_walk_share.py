"""``prefill_walk_share`` on a hand-made dispatch log: prior pages walked
over prior pages in the grid cells' tables, summed over the window's
prefill programs only, and nothing to read where the log lacks the counts
(a program that predates them) or where the program has no log."""
import types

import tiny  # sets up the import path
import harness

from repro.serving import telemetry


def _reader():
    return harness._load_module(harness.BENCH_DIR / "metrics" / "prefill_walk_share.py",
                                "bench_metric_prefill_walk_share")


def _view(t0, t1):
    return types.SimpleNamespace(steps=[types.SimpleNamespace(t0=t0, t1=t1)])


def _dispatch(program, t, walked, table):
    rec = telemetry.Dispatch(program, 1, (0, 1), prior_pages_walked=walked,
                             prior_pages_table=table)
    for i, phase in enumerate(("upload", "dispatch", "wait", "readback", "drain")):
        setattr(rec, phase, (t + i, t + i + 0.5))
    return rec


def test_share_of_a_synthetic_log(monkeypatch):
    monkeypatch.setattr(telemetry, "LOG", type(telemetry.LOG)(maxlen=telemetry.LOG_MAX))
    telemetry.LOG.extend([
        _dispatch("prefill_step", 100.0, 6, 400),
        _dispatch("decode_window_8", 110.0, 300, 300),  # not a prefill program
        _dispatch("prefill_step", 120.0, 0, 100),
        _dispatch("prefill_step", 900.0, 500, 500),  # after the window
    ])
    reader = _reader()
    view = _view(99.0, 130.0)
    assert reader.read(view) == 100.0 * 6 / 500
    assert reader.note(view) == {"prior_pages_walked": 6, "prior_pages_table": 500}
    # a window with no prefill program: nothing to read
    assert reader.read(_view(109.0, 115.0)) is None


def test_none_without_the_counts(monkeypatch):
    reader = _reader()
    summary = {"window_s": 1.0, "prefill_rows": 64}  # a log without the counts
    monkeypatch.setattr(reader.telemetry, "report", lambda t0, t1: summary)
    assert reader.read(_view(0.0, 1.0)) is None
    assert reader.note(_view(0.0, 1.0)) is None
    monkeypatch.setattr(reader, "telemetry", None)  # a program without the log
    assert reader.read(_view(0.0, 1.0)) is None
    assert reader.read(types.SimpleNamespace(steps=[])) is None
