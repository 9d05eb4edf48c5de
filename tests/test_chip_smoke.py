"""`chip_smoke.py` on the CPU: its phase functions at tiny sizes.

The script has no CPU mode — run with no TPU it must fail. What can be
checked here is that every phase's control flow, entry points and checks
are right before a chip call is spent on them: the tests pass small
dims and `interpret=True` themselves, and steer the one dispatch that
asks for the backend (`attend`'s ring branch) in the test.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY_LM = dict(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16, d_ff=128
)


def test_without_a_tpu_the_script_fails_and_says_so():
    result = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert result.returncode != 0, result.stdout + result.stderr
    last = json.loads(result.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # No phase ran: nothing but the device line precedes the verdict.
    assert "[kernels]" not in result.stdout


def test_kernels_phase_on_tiled_and_padded_lengths():
    rows = chip_smoke.phase_kernels(
        shapes=((1, 2, 256), (1, 1, 200)),
        head_dim=32, dtype="float32", interpret=True, rel_tol=1e-4,
        block_q=128, block_k=128,
    )
    assert [r["bwd"] for r in rows] == ["fused", "fused"]
    assert rows[0]["kernels"] == "flash_fwd_compact,flash_delta,flash_bwd_fused"
    # Interpreted kernels are no tpu_custom_call: the compiled-kernel
    # assertion is the chip's.
    assert rows[0]["compiled_kernel_calls"] == 0


def test_kernels_phase_rejects_a_wrong_answer(monkeypatch):
    import kubeflow_tpu.ops.attention as attention

    real = attention.dense_attention
    monkeypatch.setattr(
        attention, "dense_attention",
        lambda q, k, v, **kw: real(q, k, v, **kw) * 1.5,
    )
    with pytest.raises(AssertionError, match="max\\|o - dense f32\\|"):
        chip_smoke.phase_kernels(
            shapes=((1, 1, 128),), head_dim=32, dtype="float32",
            interpret=True, block_q=128, block_k=128,
        )


def test_train_phase_fits_checkpoints_and_reports_the_schedule():
    rows = chip_smoke.phase_train(
        model=TINY_LM,
        runs=((64, 4, "none", 4), (128, 2, "mlp", 3)),
        attention_impl="flash",
        expect_compiled_kernels=False,
    )
    first, second = rows
    assert first["restored_step"] == 4 and first["ckpt_steps"] == [1, 4]
    assert first["compile_warm_s"] >= 0 and "compile_warm_s" not in second
    # 2 layers x (fwd, delta, fused bwd).
    assert first["kernels_traced"] == 6 and first["bwd"] == "fused"
    assert second["steps"] == 3 and second["loss_first"] != second["loss_last"]


def test_train_phase_fails_when_auto_takes_the_dense_branch():
    """On the CPU `attention_impl="auto"` runs dense attention: exactly
    the silent fall the phase exists to catch."""
    with pytest.raises(AssertionError, match="no Pallas kernel"):
        chip_smoke.phase_train(
            model=TINY_LM, runs=((64, 4, "none", 3),),
            expect_compiled_kernels=False,
        )


def test_serve_phase_json_and_binary_match_module_apply():
    from kubeflow_tpu.models.resnet import tiny_resnet

    rows = chip_smoke.phase_serve(
        module=tiny_resnet(num_classes=10), image_px=32, max_batch=4,
        request_sizes=(1, 3), rel_tol=1e-4,
    )
    assert [(r["batch"], r["bucket"]) for r in rows] == [(1, 1), (3, 4)]


def test_four_chip_phases_on_virtual_devices(devices, monkeypatch):
    """The `--chips 4` phases at tiny size on the 8 virtual CPU devices:
    meshes, sharding rules and the parity and placement checks."""
    import kubeflow_tpu.ops.attention as attention
    from kubeflow_tpu.models.resnet import tiny_resnet

    # `attend` takes the ring-FLASH branch only where kernels compile;
    # steer it here so the sp case runs the kernels (interpreted) and
    # not the dense-hop ring.
    monkeypatch.setattr(attention, "kernels_compiled", lambda: True)
    rows = chip_smoke.phase_sharded_train(
        model=TINY_LM,
        cases=(("dp2_tp2", dict(dp=2, tp=2), 64, 4),
               ("sp4", dict(sp=4), 256, 2)),
        steps=3,
        attention_impl="flash",
        expect_compiled_kernels=False,
    )
    dptp, sp = rows
    assert dptp["params"]["sharded_leaves"] > 0
    assert dptp["batch_arrays"]["sharded_leaves"] > 0
    assert dptp["params"]["devices"] == sp["params"]["devices"] == 4
    assert sp["collective_permutes"] > 0 and sp["all_gathers"] == 0
    assert "flash_fwd_rect" in sp["kernels"]  # non-causal ring hops

    row = chip_smoke.phase_replicas(
        module=tiny_resnet(num_classes=10), image_px=32, max_batch=2,
        n_replicas=4, n_requests=8, rel_tol=1e-4,
    )
    assert len(set(row["weight_devices"])) == 4
    assert min(row["instances_served_per_replica"]) > 0
