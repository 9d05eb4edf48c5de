"""What keeps the program on the chip (ISSUE 23): one process per chip,
one compile-cache switch, one peaks table, kernels compiled on every
backend but the CPU, and a native build that needs no ignored file."""

import os
import subprocess
import sys

import pytest


def _python(code: str, **env) -> subprocess.CompletedProcess:
    inherited = {k: v for k, v in os.environ.items()
                 if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run(
        [sys.executable, "-c", code],
        env={**inherited, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=120,
    )


@pytest.mark.parametrize(
    "placed", [None, "/some/where/else"], ids=["in-checkout", "placed"]
)
def test_compile_cache_goes_where_the_environment_says(placed):
    """Set from outside, nothing is set in code; unset, one fixed
    directory in the checkout — the same in every process."""
    env = {"JAX_COMPILATION_CACHE_DIR": placed} if placed else {}
    code = (
        "import jax\n"
        "from kubeflow_tpu.utils.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    runs = []
    for _ in range(2):
        result = _python(code, **env)
        assert result.returncode == 0, result.stderr
        runs.append(result.stdout.split())
    assert runs[0] == runs[1]
    returned, configured = runs[0]
    assert returned == configured
    if placed:
        assert returned == placed
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert returned == os.path.join(repo, ".jax_cache")


def test_chip_peaks_know_the_v5e_and_refuse_the_rest():
    from kubeflow_tpu.train.profiling import chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.tflops_bf16, v5e.hbm_gbps) == (197.0, 819.0)
    assert "TPU v5e" in v5e.source
    for unknown in ("cpu", "TPU v4", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            chip_peaks(unknown)


def test_kernels_interpret_only_on_the_cpu_backend(monkeypatch):
    """Any backend that is not the CPU compiles the kernels and takes
    the flash branch — a platform string this code has never seen must
    reach the TPU compiler, not the interpreter or dense attention."""
    import jax

    from kubeflow_tpu.ops import flash

    assert flash._auto_interpret(None) is True  # the suite runs on cpu
    for backend in ("tpu", "some-new-accelerator"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert flash.kernels_compiled()
        assert flash._auto_interpret(None) is False
        assert flash._auto_interpret(True) is True


def test_auto_attention_says_so_when_it_falls_to_dense(devices, monkeypatch):
    """heads % tp != 0 on a mesh: `auto` still runs (dense), but never
    silently on a backend where the kernels would have compiled."""
    import jax.numpy as jnp

    from kubeflow_tpu.ops import attention
    from kubeflow_tpu.parallel import MeshSpec, build_mesh

    monkeypatch.setattr(attention, "kernels_compiled", lambda: True)
    mesh = build_mesh(MeshSpec(tp=2), devices[:2])
    q = jnp.ones((2, 16, 3, 8))
    with pytest.warns(RuntimeWarning, match="DENSE"):
        out = attention.attend(q, q, q, mesh=mesh, impl="auto")
    assert out.shape == q.shape


def test_sidecar_device_probe_never_touches_jax():
    """The sidecar is a second process beside the worker: a probe that
    initialised a JAX backend would take the chip it waits for."""
    result = _python(
        "import sys\n"
        "from kubeflow_tpu.sidecar.controller import default_device_probe\n"
        "ready = default_device_probe()\n"
        "assert 'jax' not in sys.modules, 'the probe imported jax'\n"
        "print(ready)\n"
    )
    assert result.returncode == 0, result.stderr
    # No TPU device node in this sandbox: a CPU is not "TPU ready".
    assert result.stdout.strip() == "False"


def test_process_replica_runtime_will_not_spawn_from_the_chips_holder(
    monkeypatch,
):
    from kubeflow_tpu.serving import replica

    assert replica._holds_accelerator() is False  # the suite is on cpu
    monkeypatch.setattr(replica, "_holds_accelerator", lambda: True)
    runtime = replica.ProcessReplicaRuntime(api=None, apiserver_url="http://x")
    with pytest.raises(RuntimeError, match="holds the chip"):
        runtime.ensure("r0", {})
    assert runtime.names() == []


def test_process_replica_workers_inherit_the_platform(monkeypatch):
    """No JAX_PLATFORMS override of the runtime's own: a worker runs
    where its environment (or `extra_env`) puts it."""
    from kubeflow_tpu.serving import replica

    spawned = {}

    class FakeProc:
        def poll(self):
            return None

    def fake_popen(cmd, env, **kw):
        spawned["env"] = env
        return FakeProc()

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    replica.ProcessReplicaRuntime(
        api=None, apiserver_url="http://x"
    ).ensure("r0", {})
    assert spawned["env"]["JAX_PLATFORMS"] == "cpu"  # inherited
    monkeypatch.delenv("JAX_PLATFORMS")
    replica.ProcessReplicaRuntime(
        api=None, apiserver_url="http://x", extra_env={"A": "b"}
    ).ensure("r1", {})
    assert "JAX_PLATFORMS" not in spawned["env"]
    assert spawned["env"]["A"] == "b"


def test_native_build_dir_from_another_root_is_detected(tmp_path, monkeypatch):
    from kubeflow_tpu.native import build

    fake_native = tmp_path / "native"
    fake_build = fake_native / "build"
    fake_build.mkdir(parents=True)
    monkeypatch.setattr(build, "_NATIVE", fake_native)
    monkeypatch.setattr(build, "_BUILD", fake_build)
    assert not build._foreign_build_dir()  # never configured
    cache = fake_build / "CMakeCache.txt"
    cache.write_text(f"CMAKE_HOME_DIRECTORY:INTERNAL={fake_native}\n")
    assert not build._foreign_build_dir()
    cache.write_text("CMAKE_HOME_DIRECTORY:INTERNAL=/somewhere/else/native\n")
    assert build._foreign_build_dir()
