"""bench.py — one configuration, one process: it refuses a platform it was
not asked for, and its one JSON line names the device it ran on.  Plus the
comm-volume HLO parser."""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_refuses_the_wrong_platform():
    """Asked for the TPU (the default) on a CPU host: exit 3, no JSON — a
    CPU number is never printed under the headline metric's name."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, (proc.stdout, proc.stderr[-2000:])
    assert proc.stdout.strip() == ""


def test_bench_one_json_line_names_its_device(monkeypatch, capsys):
    monkeypatch.syspath_prepend(_REPO)
    import bench

    assert bench.main([
        "--platform", "cpu", "--image-size", "64", "--num-layers", "3",
        "--num-filters", "16", "--scan", "1", "--iters", "2",
    ]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert out["platform"] == "cpu" and out["device_kind"]
    assert out["value"] > 0 and out["iters"] == 2
    # not the 1024² bs1 configuration: no comparison with the reference
    assert out["vs_baseline"] is None


def test_hlo_collective_stats_parsing():
    """comm_volume_report's HLO parser: counts each collective once (start
    form preferred), sums output bytes, tuples summed per element."""
    sys.path.insert(0, os.path.join(_REPO, "benchmarks", "communication"))
    from comm_volume_report import hlo_collective_stats

    hlo = """
  %x = bf16[2,16,16,8]{3,2,1,0} collective-permute(%a), source_target_pairs={{0,1}}
  %y = (f32[128]{0}, f32[128]{0}) all-reduce-start(%b, %c), replica_groups={}
  %z = (f32[128]{0}, f32[128]{0}) all-reduce-done(%y)
  ROOT %w = f32[64,4]{1,0} all-gather(%d), dimensions={1}
  %v = (bf16[2,16,16,8]{3,2,1,0}, bf16[2,16,16,8]{3,2,1,0}, u32[], u32[]) collective-permute-start(%g)
  %u = (f32[64]{0}, f32[256]{0}) all-gather-start(%h), dimensions={0}
  %notacoll = f32[8]{0} add(%e, %f)
"""
    s = hlo_collective_stats(hlo)
    # sync permute + async permute-start (multi-dim tuple; result entry)
    assert s["collective-permute"]["count"] == 2
    assert s["collective-permute"]["bytes"] == 2 * (2 * 16 * 16 * 8 * 2)
    # async start tuple = (operand, result): count the RESULT once
    assert s["all-reduce"]["count"] == 1
    assert s["all-reduce"]["bytes"] == 128 * 4
    # ROOT-prefixed sync all-gather + async all-gather-start: both report
    # the (group-factor-carrying) output bytes
    assert s["all-gather"]["count"] == 2
    assert s["all-gather"]["bytes"] == 64 * 4 * 4 + 256 * 4
    assert s["total_count"] == 5
