"""Perf-regression sentinel (docs/metrics.md, "perfwatch"): the EWMA
baseline flags an injected 2x step-time regression at the right row, a
±5% noise trace stays quiet, the changepoint localizes the regime
shift, the schema guard refuses mixed row formats, and the --budget CLI
gate exits nonzero exactly when a watched series regressed."""

import json
import os
import random
import subprocess
import sys

import pytest

from horovod_tpu.telemetry import perfwatch

pytestmark = pytest.mark.quick


def _noisy(base, n, jitter, seed):
    rng = random.Random(seed)
    return [base * (1 + rng.uniform(-jitter, jitter)) for _ in range(n)]


def test_injected_2x_regression_flagged_at_index():
    series = _noisy(0.100, 12, 0.03, seed=3) + _noisy(0.200, 8, 0.03,
                                                      seed=4)
    d = perfwatch.detect(series, direction="up")
    assert d["regressed"], d
    assert d["index"] == 12, d
    assert d["ratio"] > 1.8, d
    # Baseline stays frozen at the pre-regression level: the slow
    # regime must not teach it that slow is normal.
    assert d["baseline"] < 0.12, d


def test_noise_trace_stays_quiet():
    series = _noisy(0.100, 40, 0.05, seed=11)
    d = perfwatch.detect(series, direction="up")
    assert not d["regressed"], d
    # Same for the down direction (busbw/efficiency series).
    assert not perfwatch.detect(series, direction="down")["regressed"]


def test_single_outlier_not_flagged():
    """One GC pause must not gate CI: flagging needs `consecutive`
    breaches in a row."""
    series = _noisy(0.100, 10, 0.02, seed=5) + [0.300] \
        + _noisy(0.100, 10, 0.02, seed=6)
    assert not perfwatch.detect(series, direction="up")["regressed"]


def test_flagged_ratio_not_polluted_by_earlier_outlier():
    """A transient unflagged outlier must not leave its magnitude in
    the verdict: `ratio` describes the FLAGGED regression."""
    series = ([1.0] * 6 + [3.0]            # lone 3x outlier, no flag
              + [1.0] * 6 + [1.4, 1.4, 1.4])  # the real 1.4x regression
    d = perfwatch.detect(series, direction="up")
    assert d["regressed"] and d["index"] == 13, d
    assert d["ratio"] < 2.0, d  # 1.4x-ish, not the outlier's 3x


def test_down_direction_for_efficiency_series():
    series = [0.8] * 10 + [0.3] * 5
    d = perfwatch.detect(series, direction="down")
    assert d["regressed"] and d["index"] == 10, d


def test_changepoint_localizes_shift():
    series = [1.0] * 9 + [2.0] * 7
    index, shift = perfwatch.changepoint(series)
    assert index == 9, index
    assert shift == 2.0, shift
    assert perfwatch.changepoint([1.0, 2.0]) == (None, 1.0)


def test_schema_guard_refuses_mixed_rows():
    rows = [{"metric": "a", "schema": 1}, {"metric": "b", "schema": 2}]
    with pytest.raises(SystemExit, match="MIXED schema"):
        perfwatch.check_schema(rows)
    # Uniform (or absent = legacy 0) stamps pass.
    assert perfwatch.check_schema([{"metric": "a", "schema": 1}]) == 1
    assert perfwatch.check_schema([{"metric": "a"}]) == 0


def test_scraper_series_derivation():
    """Interval series from cumulative scraper snapshots: busbw from
    wire tx deltas, overlap efficiency from ledger deltas, step time
    from ledger step-count deltas."""
    rows = []
    for i in range(4):
        rows.append({
            "ts": 10.0 * i,
            "wire": {
                "tx_bytes": int(5e9) * i,
                "overlap": {
                    "steps": 100 * i,
                    "intra": {"hidden_us": 600_000 * i,
                              "total_us": 1_000_000 * i},
                    "cross": {"hidden_us": 0, "total_us": 0},
                },
            },
        })
    s = perfwatch.scraper_series(rows)
    assert s[("scrape", "busbw_gbps")] == [0.5, 0.5, 0.5]
    assert s[("scrape", "overlap_efficiency")] == [0.6, 0.6, 0.6]
    assert s[("scrape", "step_time_ms")] == [100.0, 100.0, 100.0]


def test_real_bench_row_shapes_are_watchable():
    """The gate must bite on the rows a lane ACTUALLY emits: per-size
    busbw lives in a nested `points` list, step time is `step_s`, and
    the MFU headline is the generic `value` (down = regression only
    because the metric name says mfu)."""
    rows = []
    for r in range(6):
        rows.append({
            "metric": "ring_busbw", "config": "overlap", "ranks": 2,
            "schema": 1,
            "points": [
                {"payload_bytes": 1 << 24,
                 "busbw_gbps": 0.66 if r < 4 else 0.22,
                 "step_s": 0.05},
                {"payload_bytes": 1 << 20, "busbw_gbps": 0.30,
                 "step_s": 0.007},
            ]})
        rows.append({"metric": "llama_train_step_mfu", "schema": 1,
                     "value": 0.69, "vs_baseline": 1.7})
    s = perfwatch.bench_series(rows)
    # Per-size points become their own series (no 16MiB/1MiB regime
    # interleaving), keyed by the full row identity.
    k16 = ("ring_busbw/overlap/2/16777216", "busbw_gbps")
    assert s[k16] == [0.66] * 4 + [0.22] * 2, sorted(s)
    assert len(s[("ring_busbw/overlap/2/1048576", "busbw_gbps")]) == 6
    # The 16 MiB collapse is flagged; the MFU headline is watched via
    # `value` and stays quiet.
    verdicts = perfwatch.watch(s)
    flagged = {(v["metric"], v["field"]): v["regressed"]
               for v in verdicts}
    assert flagged[k16] is True, verdicts
    assert flagged[("llama_train_step_mfu", "value")] is False
    # `value` on a metric whose name says nothing is NOT watchable
    # (direction unknown — flagging it would alarm on unit changes).
    assert perfwatch.field_direction("llama_update_sweep",
                                     "value") is None
    assert perfwatch.field_direction("llama_train_step_mfu",
                                     "value") == "down"


def _write_rows(path, values, field="mean_step_s", metric="eager"):
    with open(path, "w") as f:
        for v in values:
            f.write(json.dumps(
                {"metric": metric, field: v, "schema": 1}) + "\n")
    return str(path)


def test_budget_cli_gates_on_regression(tmp_path, capsys):
    reg = _write_rows(tmp_path / "reg.jsonl",
                      [0.1] * 10 + [0.2] * 5)
    assert perfwatch.main(["--bench", reg, "--budget"]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "at row 10" in out, out
    quiet = _write_rows(tmp_path / "quiet.jsonl",
                        _noisy(0.1, 20, 0.05, seed=9))
    assert perfwatch.main(["--bench", quiet, "--budget"]) == 0
    out = capsys.readouterr().out
    assert "ok" in out and "REGRESSED" not in out, out


def test_budget_gate_fails_on_zero_watchable_series(tmp_path, capsys):
    """A gate with nothing to gate on fails distinctly (exit 2): a
    renamed field or a wrong path must not ship a regression under a
    green check — same fail-loud rule as the schema guard."""
    p = tmp_path / "renamed.jsonl"
    p.write_text(json.dumps(
        {"metric": "eager", "renamed_step_field": 0.1, "schema": 1})
        + "\n")
    assert perfwatch.main(["--bench", str(p), "--budget"]) == 2
    # Report mode (no gate) still exits 0 on the same input.
    assert perfwatch.main(["--bench", str(p)]) == 0


def test_budget_cli_json_rows(tmp_path, capsys):
    reg = _write_rows(tmp_path / "reg.jsonl", [1.0] * 8 + [2.5] * 4)
    assert perfwatch.main(["--bench", reg, "--json"]) == 0  # report mode
    rows = [json.loads(line)
            for line in capsys.readouterr().out.splitlines()]
    assert rows and rows[0]["regressed"], rows
    assert rows[0]["changepoint_index"] == 8, rows


def _diff_cmd(*args):
    """``python -m horovod_tpu.telemetry.perfwatch --diff ...`` as a
    user types it: (exit code, stdout, stderr)."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.telemetry.perfwatch",
         "--diff", *args], cwd=repo, text=True, capture_output=True,
        timeout=120)
    return out.returncode, out.stdout, out.stderr


def test_diff_command_prints_relative_change(tmp_path):
    old = _write_rows(tmp_path / "old.jsonl", [0.100])
    new = _write_rows(tmp_path / "new.jsonl", [0.125])
    rc, out, err = _diff_cmd(old, new)
    assert rc == 0, (rc, out, err)
    row = [ln for ln in out.splitlines() if "step_s" in ln]
    assert row and "+25.0%" in row[0] and "eager" in row[0], out
    assert "worst relative change +25.0%" in out, out
    # Under the threshold the field is hidden, the exit code the same.
    rc, out, err = _diff_cmd(old, new, "--diff-threshold", "0.5")
    assert rc == 0 and "step_s" not in out, (rc, out, err)


def test_diff_command_refuses_mismatched_schema_stamps(tmp_path):
    old = _write_rows(tmp_path / "old.jsonl", [0.1])
    new = tmp_path / "new.jsonl"
    new.write_text(json.dumps(
        {"metric": "eager", "step_s": 0.1, "schema": 2}) + "\n")
    rc, out, err = _diff_cmd(old, str(new))
    assert rc != 0, (rc, out, err)
    assert "refusing to compare schema 1" in err, err
    assert "step_s" not in out, out
