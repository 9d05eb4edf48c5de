"""CI utils (#27), releasing (#28), tools/scripts (#29)."""

import pathlib
import subprocess
import sys

import yaml

from kubeflow_tpu.api.workflow import WorkflowSpec
from kubeflow_tpu.ci.application_util import (
    MANIFEST_DIR,
    manifest_drift,
    regenerate_manifests,
    set_bundle_images,
)
from kubeflow_tpu.deploy.bundles import BUNDLES, bundle_resources
from kubeflow_tpu.deploy.kfdef import default_spec

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from releasing.hubsync import sync  # noqa: E402
from releasing.releaser import IMAGES, release_workflow  # noqa: E402


# -- manifests (regenerate_manifest_tests analog) --------------------------


def test_checked_in_manifests_match_generator():
    """The drift gate the reference ran in CI: goldens must equal the
    generator's output. Run `python -m kubeflow_tpu.ci regenerate` after
    changing bundles."""
    assert MANIFEST_DIR.exists(), "manifests/ goldens not generated"
    assert manifest_drift() == []


def test_regenerate_into_tmp(tmp_path):
    written = regenerate_manifests(tmp_path)
    assert {p.stem for p in written} == set(BUNDLES)
    docs = list(yaml.safe_load_all((tmp_path / "tpujob-operator.yaml").read_text()))
    assert any(d["kind"] == "CustomResourceDefinition" for d in docs)
    # Stale golden cleanup
    (tmp_path / "gone-bundle.yaml").write_text("x: 1\n")
    regenerate_manifests(tmp_path)
    assert not (tmp_path / "gone-bundle.yaml").exists()


def test_set_bundle_images_retags():
    resources = bundle_resources(default_spec(), ["centraldashboard"])
    set_bundle_images(
        resources, {"kubeflow-tpu/centraldashboard": "gcr.io/x/dash:v9"}
    )
    deployments = [r for r in resources if r.kind == "Deployment"]
    images = [
        c["image"]
        for r in deployments
        for c in r.spec["template"]["spec"]["containers"]
    ]
    assert "gcr.io/x/dash:v9" in images


# -- releasing -------------------------------------------------------------


def test_release_workflow_dag():
    wf = release_workflow("v1.0.0")
    spec = WorkflowSpec.from_dict(wf.spec)  # validates incl. cycles
    names = {s.name for s in spec.steps}
    for image, _, _ in IMAGES:
        assert f"build-{image}" in names and f"push-{image}" in names
    test_step = spec.step("test")
    assert set(test_step.dependencies) == {
        f"build-{n}" for n, _, _ in IMAGES
    }
    assert spec.step("tag-release").dependencies == tuple(
        f"push-{n}" for n, _, _ in IMAGES
    )
    assert spec.on_exit is not None


def test_hubsync_copies_all_images():
    calls = []
    pairs = sync(
        "v2", source="gcr.io/src", dest="docker.io/dst",
        copy=lambda s, d: calls.append((s, d)),
    )
    assert calls == pairs
    assert ("gcr.io/src/platform:v2", "docker.io/dst/platform:v2") in pairs
    assert len(pairs) == len(IMAGES)


# -- scripts/tools ---------------------------------------------------------


def test_boilerplate_checker(tmp_path):
    sys.path.insert(0, str(REPO / "scripts"))
    import check_boilerplate

    good = tmp_path / "good.py"
    good.write_text('"""Documented."""\nx = 1\n')
    bad = tmp_path / "bad.py"
    bad.write_text("x = 1\n")
    script = tmp_path / "s.sh"
    script.write_text("#!/bin/bash\n# does things\ntrue\n")
    assert check_boilerplate.check(tmp_path) == ["bad.py"]
    # License mode: verbatim header required.
    lic = "Copyright 2026"
    good.write_text(f"# {lic}\nx = 1\n")
    bad2 = check_boilerplate.check(tmp_path, license_text=lic)
    assert "good.py" not in bad2 and "bad.py" in bad2


def test_repo_passes_its_own_boilerplate_policy():
    result = subprocess.run(
        [sys.executable, "scripts/check_boilerplate.py", "--root", "kubeflow_tpu"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, result.stdout + result.stderr


def _engine_rule_clean(rule_id: str) -> None:
    """Thin wrapper: assert one kftpu-lint engine rule runs clean over
    the repo. The regex lints that used to live inline here migrated
    onto `kubeflow_tpu/ci/lint/` (ISSUE 8); these named tests remain so
    every CHANGES-referenced guard stays discoverable under its
    historical name, now enforcing the same contract through the
    engine (fixture-verified in tests/test_lint_engine.py)."""
    from kubeflow_tpu.ci.lint import lint_repo

    result = lint_repo(rules=[rule_id])
    assert result.clean, "\n" + result.render()


def test_no_deepcopy_in_dispatch_or_fanout_paths():
    """Perf gate (docs/perf.md) → engine rule `no-deepcopy-hot-path`:
    no deepcopy in the fan-out/read hot paths of either store backend
    (one creeping back silently restores O(watchers x events)
    copying)."""
    _engine_rule_clean("no-deepcopy-hot-path")


def test_flash_attention_hot_path_stays_blockwise():
    """Perf gate (docs/perf.md, ISSUE 3) → engine rule
    `flash-blockwise`: no einsum / no [S, S]-shaped kernel output /
    lane-packed lse helpers present in ops/flash.py."""
    _engine_rule_clean("flash-blockwise")


def test_fused_flash_bwd_shared_delta_and_single_kv_pass():
    """Perf gate (docs/perf.md, ISSUE 7) → engine rule
    `fused-kernel-streams` (ref streams pinned, no o_ref) plus the
    schedule-model half of the contract via the same `flash_schedule`
    accounting every bench shares: single KV pass when fused, two
    passes when not, fused bytes well under two-pass at deep
    triangles. (The traced-program half — fused kernel engaged in the
    grad jaxpr, remat no-forward-rerun — is the `fused-flash-grad`
    program contract in tests/test_program_contracts.py.)"""
    from kubeflow_tpu.ops import flash

    _engine_rule_clean("fused-kernel-streams")

    # The flagship deep triangle (nq = 16, default 1024-blocks, packed
    # lse): the byte ratio only means something there.
    fused = flash.flash_schedule(16384, 16384)
    assert fused["bwd_fused"], fused
    assert fused["bwd_total_grid_steps"] == fused["grid_steps"], (
        "fused backward no longer single-KV-pass: "
        f"{fused['bwd_total_grid_steps']} total vs "
        f"{fused['grid_steps']} per pass"
    )
    two_pass = flash.flash_schedule(16384, 16384, causal=False)
    assert not two_pass["bwd_fused"]
    assert (
        two_pass["bwd_total_grid_steps"] == 2 * two_pass["grid_steps"]
    )
    assert (
        fused["bwd_hbm_bytes_fused"]
        <= 0.62 * fused["bwd_hbm_bytes_two_pass"]
    ), fused


def test_pipeline_hot_path_psums_scalars_only():
    """Perf gate (docs/perf.md, ISSUE 4) → engine rule
    `scalar-psum-only`: the ONLY `lax.psum` in parallel/pipeline.py is
    the scalar loss reduction, and models/transformer.py adds none.
    (The compiled-HLO half — no activation-sized all-reduce across pp
    — is the `pipeline-wire-*` program contract.)"""
    _engine_rule_clean("scalar-psum-only")


def test_train_loop_never_swallows_interrupts():
    """Robustness gate (docs/resilience.md, ISSUE 5) → engine rule
    `no-interrupt-swallow`: nothing under train/ catches bare /
    BaseException / KeyboardInterrupt / SystemExit — preemption flows
    to fit()'s step-boundary handler. The repo-wide `no-bare-except`
    rule (tests/test_lint_clean.py) generalizes the bare/BaseException
    half to every package."""
    _engine_rule_clean("no-interrupt-swallow")


def test_resilience_soak_is_slow_marked_with_seeded_nightly_entry():
    """The kill-and-resume soak follows the chaos-soak convention: the
    nightly variant is `slow`-marked (tier-1 runs only the small
    deterministic soak) and `bench.py --workload resilience` drives it
    with a printed seed so any failure reproduces from one integer."""
    soak = (
        REPO / "tests" / "e2e" / "test_train_resilience_e2e.py"
    ).read_text()
    assert "@pytest.mark.slow" in soak
    assert "KFTPU_RESILIENCE_SEED" in soak
    bench = (REPO / "bench.py").read_text()
    assert "test_resilience_soak_nightly" in bench
    assert "KFTPU_RESILIENCE_SEED" in bench
    # The seed is printed up front (the repro contract).
    assert "resilience soak seed=" in bench


def test_elastic_resize_soak_is_slow_marked_with_seeded_nightly_entry():
    """The elastic-resize soak (ISSUE 9) follows the same convention as
    the kill-and-resume and failover soaks: tier-1 runs the small
    fixed-seed shrink->grow cycle, the dense nightly variant is
    `slow`-marked, and `bench.py --workload resilience` drives it with
    a printed seed (publishing the `resilience_*_elastic` rows) so any
    failure reproduces from one integer."""
    soak = (
        REPO / "tests" / "e2e" / "test_train_resilience_e2e.py"
    ).read_text()
    assert "def test_resilience_soak_elastic_resize" in soak
    nightly = soak.split("def test_resilience_soak_elastic_nightly")
    assert len(nightly) == 2
    assert nightly[0].rstrip().endswith("@pytest.mark.slow")
    assert "KFTPU_RESILIENCE_SEED" in soak
    bench = (REPO / "bench.py").read_text()
    assert "test_resilience_soak_elastic_nightly" in bench
    assert "resilience_goodput_elastic" in bench
    assert "resilience_steps_lost_per_kill_elastic" in bench
    # The seed is printed up front (the repro contract).
    assert "resilience soak seed=" in bench


def test_failover_soak_is_slow_marked_with_seeded_nightly_entry():
    """The apiserver-failover soak follows the same convention as the
    chaos and resilience soaks: the kill-cycle nightly is `slow`-marked
    (tier-1 runs only the single-kill deterministic e2e) and `bench.py
    --workload controlplane` drives it with a printed seed so any
    failure reproduces from one integer."""
    soak = (
        REPO / "tests" / "e2e" / "test_apiserver_failover_e2e.py"
    ).read_text()
    assert "@pytest.mark.slow" in soak
    assert "KFTPU_FAILOVER_SEED" in soak
    bench = (REPO / "bench.py").read_text()
    assert "test_failover_soak_nightly" in bench
    assert "KFTPU_FAILOVER_SEED" in bench
    # The seed is printed up front (the repro contract).
    assert "failover soak seed=" in bench


def test_rl_soak_is_slow_marked_with_seeded_nightly_entry():
    """The RL study soak (ISSUE 12) follows the same convention as the
    chaos/resilience/failover soaks: tier-1 runs the small fixed-seed
    study, the nightly variant is `slow`-marked, and `bench.py
    --workload rl` drives it with a printed seed so any failure
    reproduces from one integer."""
    soak = (REPO / "tests" / "e2e" / "test_rl_soak_e2e.py").read_text()
    assert "@pytest.mark.slow" in soak
    assert "KFTPU_RL_SEED" in soak
    nightly = soak.split("def test_rl_soak_nightly")
    assert len(nightly) == 2
    assert nightly[0].rstrip().endswith("@pytest.mark.slow")
    bench = (REPO / "bench.py").read_text()
    assert "test_rl_soak_nightly" in bench
    assert "KFTPU_RL_SEED" in bench
    # The seed is printed up front (the repro contract).
    assert "rl soak seed=" in bench


def test_clients_built_from_config_take_endpoint_lists():
    """Resilience gate (docs/resilience.md, ISSUE 6) → engine rule
    `endpoint-list-clients`: every `HttpApiClient` built from
    operator-supplied config (`--apiserver`/`--server` flags, the e2e
    workers' KFTPU_APISERVER env) parses it with `endpoints_from_env`
    — that value IS the endpoint-list channel for active-passive HA
    pairs, and a bare `HttpApiClient(args.apiserver)` loses the
    failover the HA deployment exists to provide."""
    _engine_rule_clean("endpoint-list-clients")


def test_gcb_template():
    result = subprocess.run(
        [sys.executable, "tools/gcb/template.py", "--commit", "abc123"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, result.stderr
    doc = yaml.safe_load(result.stdout)
    assert len(doc["steps"]) == len(IMAGES)
    assert all(img.endswith(":abc123") for img in doc["images"])


def test_releaser_cli_emits_valid_workflow():
    result = subprocess.run(
        [sys.executable, "releasing/releaser.py", "--version", "v9.9.9"],
        cwd=REPO,
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, result.stderr
    doc = yaml.safe_load(result.stdout)
    WorkflowSpec.from_dict(doc["spec"])  # validates
