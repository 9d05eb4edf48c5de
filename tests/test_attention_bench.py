"""CI smoke for `bench.py --workload attention` (docs/perf.md): the
kernel microbench must run end-to-end at tiny interpreted shapes and emit
driver-parsable JSON metric lines, including the schedule accounting the
attention overhaul is gated on (compact grid steps, packed lse bytes)."""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_attention_bench_smoke_emits_parsable_metrics():
    result = subprocess.run(
        [
            sys.executable, "bench.py", "--workload", "attention",
            "--attn-seq-lens", "128,256", "--steps", "1",
            "--warmup-steps", "1", "--batch-size", "1",
            "--head-dim", "32", "--attn-heads", "2",
            "--flash-block-q", "128", "--flash-block-k", "128",
            "--roofline-seq", "128", "--roofline-batch", "1",
            "--roofline-layers", "2", "--roofline-d-model", "64",
            "--roofline-d-ff", "128", "--roofline-vocab", "512",
        ],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=280,
    )
    assert result.returncode == 0, result.stdout + result.stderr
    metrics = {}
    for line in result.stdout.splitlines():
        if not line.startswith("{"):
            continue
        m = json.loads(line)
        # The driver's parse contract — same shape as every other bench.
        assert set(m) == {"metric", "value", "unit", "vs_baseline"}, m
        assert isinstance(m["value"], (int, float)) and m["value"] > 0, m
        metrics[m["metric"]] = m
    for s in (128, 256):
        for stem in (
            "attention_flash_fwd_tflops",
            "attention_flash_fwdbwd_tflops",
            "attention_causal_grid_steps",
            "attention_lse_hbm_bytes",
            "attention_bwd_hbm_bytes",
        ):
            assert f"{stem}_s{s}" in metrics, (stem, s, sorted(metrics))
    # The schedule accounting must show the overhaul: at S=256 with
    # 128-wide blocks the compact grid runs 3 of the rectangle's 4
    # steps. The lse packs to 1/128th the replicated bytes where the
    # packed block is a legal TPU tile — at S=128 the one block spans
    # the array; at S=256 a (1, 128) block of a (2, 128) array is not,
    # so that length reports the replicated layout.
    grid = metrics["attention_causal_grid_steps_s256"]
    assert grid["value"] == 3 and grid["vs_baseline"] == 0.75, grid
    lse = metrics["attention_lse_hbm_bytes_s128"]
    assert abs(lse["vs_baseline"] - 1 / 128) < 1e-6, lse
    assert "lane-packed" in lse["unit"], lse
    lse = metrics["attention_lse_hbm_bytes_s256"]
    assert lse["vs_baseline"] == 1.0 and "lane-replicated" in lse["unit"]
    # Dense ran at these lengths, so the TFLOP/s rows carry a real ratio.
    assert metrics["attention_flash_fwd_tflops_s256"]["vs_baseline"] > 0
    # Fused one-pass backward (ISSUE 7): the bwd HBM-byte row's ratio is
    # the fused/two-pass fraction — strictly < 1 whenever fused engages
    # (these shapes fuse; the run would have FAILED on the jaxpr gate if
    # dispatch and accounting drifted), and the unit names the path.
    for s in (128, 256):
        bwd = metrics[f"attention_bwd_hbm_bytes_s{s}"]
        assert 0 < bwd["vs_baseline"] < 1, bwd
        assert "fused one-pass" in bwd["unit"], bwd
    # Per-phase roofline rows (the mechanical docs/architecture.md
    # table): one ms row per phase, unit carrying TFLOP/GB/bound.
    for phase in ("attn_fwd", "attn_bwd", "mlp", "optimizer"):
        row = metrics[f"roofline_{phase}_ms_s128"]
        assert row["value"] > 0, row
        assert "bound:" in row["unit"], row
    # The roofline table itself rides stderr for humans.
    assert "| phase | ms | TFLOP | GB moved |" in result.stderr
    assert "roofline saturated phase" in result.stderr
