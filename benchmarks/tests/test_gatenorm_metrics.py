"""`gatenorm_time_pct.train` and `gatenorm_roofline.train` (PR 43) on
hand-made `Op` tuples and a hand-made table of scopes: a number at PR 42's
kind of program (XLA's passes under `ssm.gate_norm` / `kda.gate_norm`) and
at the kernel pair's, None in a cell with no recurrent mixer;
`lib/flops_gatenorm.py`'s bytes for the two cells by hand."""

import collections
import json
import pathlib

import pytest

from benchmarks.lib import flops_gatenorm, loader, program_trace as pt, scopes

ROOT = pathlib.Path(__file__).resolve().parents[2]
KIMI = "kimi-linear-48b-a3b-ep32.train-8k"
NEMOTRON = "nemotron-3-super-tp2ep64.train-8k"
NAMES = ("gatenorm_time_pct.train", "gatenorm_roofline.train")
Scope = collections.namedtuple("Scope", "path phase kind mixed", defaults=((),))

# One step of 1000 ns, twice; every instruction runs once a step.
XLA = {  # PR 42's program: elementwise passes under the scopes
    "fusion.1": (Scope("layer_8/ssm/ssm.in_proj", "forward", "matmul"), 300),
    "fusion.2": (Scope("layer_8/ssm/ssm.gate_norm", "forward", "elementwise"), 80),
    "fusion.3": (Scope("layer_8/ssm/ssm.gate_norm", "recompute", "elementwise"), 40),
    "fusion.4": (Scope("layer_8/ssm/ssm.gate_norm", "backward", "elementwise"), 160),
    "ssd_fwd.5": (Scope("layer_8/ssm/ssm.scan/ssd_fwd", "forward", "kernel"), 100),
    "fusion.6": (Scope("layer_1/kda/kda.gate_norm/wg_b", "forward", "matmul"), 20),
    "fusion.7": (Scope("head/bsd,vd->bsv", "forward", "matmul"), 300),
}
KERNELS = {  # PR 43's: the pair, and what XLA does round it
    "fusion.1": XLA["fusion.1"],
    "gatenorm_fwd.2": (
        Scope("layer_8/ssm/ssm.gate_norm/gatenorm_fwd", "forward", "kernel"), 16),
    "gatenorm_bwd.3": (
        Scope("layer_8/ssm/ssm.gate_norm/gatenorm_bwd", "backward", "kernel"), 32),
    "fusion.4": (Scope("layer_8/ssm/ssm.gate_norm", "forward", "elementwise"), 4),
    "ssd_fwd.5": XLA["ssd_fwd.5"],
    "fusion.6": XLA["fusion.6"],
    "gatenorm_bwd.8": (
        Scope("layer_1/kda/kda.gate_norm/gatenorm_bwd", "backward", "kernel"), 8),
    "fusion.7": XLA["fusion.7"],
}


class _Reduced:
    def __init__(self, core, step_ns=1000):
        self.window_ns = (0, 2 * step_ns)
        self.busy_ns = {0: sum(o.end - o.start for o in core)}
        self.module_events = {0: [
            ("jit_train_step", 0, step_ns - 10),
            ("jit_train_step", step_ns, 2 * step_ns - 10),
        ]}

    def main_module(self, device=0):
        return "jit_train_step"


def _read(program: dict, cell: dict, monkeypatch, step_ns=1000) -> dict:
    from kubeflow_tpu.train import profiling

    ops = [
        pt.Op(name, "fusion", "", step * step_ns + at, step * step_ns + at + ns,
              pt.CORE_LINE)
        for step in range(2)
        for at, (name, (_, ns)) in zip(
            range(0, step_ns, step_ns // 10), program.items()
        )
    ]
    table = {name: scope for name, (scope, _) in program.items()}
    monkeypatch.setattr(profiling, "_STEP_PROGRAMS", {"jit_train_step": lambda: table})
    monkeypatch.setattr(scopes, "_made", {})
    monkeypatch.setattr(scopes, "_device_bytes", lambda: 0)
    monkeypatch.setattr(
        pt, "of_cell", lambda c: pt.ProgramTrace({0: ops}, [], [], {})
    )
    return {
        name: loader.load_metric(name).read(_Reduced(ops, step_ns), [], cell)
        for name in NAMES
    }


def _cell(name: str) -> dict:
    cell = loader.load_cell(name, loader.load_benchmark(ROOT))
    numbers = cell["driver"].model_numbers(cell["config"])
    return {**cell, "facts": {"numbers": numbers, "device_kind": "TPU v5 lite"}}


def test_the_cells_bytes_by_hand():
    """Nemotron: 5 state-space layers x (4 + 7) arrays of [8192, 4096] at 2
    bytes, 3.69 GB a step (4.51 ms at 819 GB/s); kimi: 4 delta layers x
    (3 + 5) arrays of [8192, 4096], 2.15 GB (2.62 ms)."""
    kimi, nemotron = _cell(KIMI), _cell(NEMOTRON)
    assert flops_gatenorm.gated_layers(nemotron["facts"]["numbers"]) == (5, 4096, 11)
    assert flops_gatenorm.gated_layers(kimi["facts"]["numbers"]) == (4, 4096, 8)
    assert flops_gatenorm.gatenorm_bytes(
        nemotron["facts"]["numbers"], 8192
    ) == 5 * 11 * 8192 * 4096 * 2 == 3_690_987_520
    assert flops_gatenorm.gatenorm_bytes(
        kimi["facts"]["numbers"], 8192
    ) == 4 * 8 * 8192 * 4096 * 2 == 2_147_483_648
    assert flops_gatenorm.gated_layers({"hidden_size": 2048}) == (0, 0, 0)
    assert flops_gatenorm.gatenorm_bytes({"hidden_size": 2048}, 8192) == 0.0


@pytest.mark.parametrize("cell, needed", [
    (NEMOTRON, 3_690_987_520), (KIMI, 2_147_483_648),
])
@pytest.mark.parametrize("program, under", [(XLA, 300), (KERNELS, 80)])
def test_a_number_at_both_kinds_of_program(program, under, cell, needed, monkeypatch):
    """Every phase under either scope, the gate's thin products and the
    kernels' events with them; the roofline by the cell's bytes over that
    time a step."""
    busy = sum(ns for _, ns in program.values())
    read = _read(program, _cell(cell), monkeypatch)
    assert read["gatenorm_time_pct.train"] == pytest.approx(100.0 * under / busy)
    assert read["gatenorm_roofline.train"] == pytest.approx(
        100.0 * (needed / 819e9) / (under * 1e-9)
    )


def test_the_share_of_the_roofline_cannot_pass_100(monkeypatch):
    """A step whose gated norms took exactly the memory's least time for
    the cell's bytes reads 100; anything an implementation adds to the
    scope (a pass of XLA's beside the pair) lowers it."""
    cell = _cell(NEMOTRON)
    least_ns = round(3_690_987_520 / 819e9 * 1e9)  # 4.5 ms
    at_the_least = {"gatenorm_fwd.1": (
        Scope("layer_8/ssm/ssm.gate_norm/gatenorm_fwd", "forward", "kernel"),
        least_ns,
    )}
    slower = dict(at_the_least, **{"fusion.2": (
        Scope("layer_8/ssm/ssm.gate_norm", "forward", "elementwise"), 1000,
    )})
    roofline = lambda program: _read(
        program, cell, monkeypatch, step_ns=100_000_000
    )["gatenorm_roofline.train"]
    assert roofline(at_the_least) == pytest.approx(100.0, rel=1e-6)
    assert 99.9 < roofline(slower) < 100.0


def test_none_without_a_recurrent_mixer_and_none_without_a_table(monkeypatch):
    plain = _cell("xing4.0-29b-a4b-ep8.train-8k")
    read = _read({"fusion.7": XLA["fusion.7"]}, plain, monkeypatch)
    assert read == dict.fromkeys(NAMES)
    # a mixer's cell whose program registers no step: no table, None
    cell = _cell(NEMOTRON)
    _read(XLA, cell, monkeypatch)
    from kubeflow_tpu.train import profiling

    monkeypatch.setattr(profiling, "_STEP_PROGRAMS", {})
    monkeypatch.setattr(scopes, "_made", {})
    for name in NAMES:
        assert loader.load_metric(name).read(_Reduced([]), [], cell) is None
    under = loader.load_metric("gatenorm_time_pct.train").in_gate_norm
    assert under("kda/kda.gate_norm/wg_a") and under("ssm/ssm.gate_norm/gatenorm_bwd")
    assert under("gatenorm_fwd")
    assert not under("kda/kda.conv/shortconv_fwd") and not under("ssm/ssm.scan/ssd_fwd")
    assert not under("kda/kda.gates")


def test_the_kernels_names_are_in_no_other_kernel_metric():
    """`kda_*`, `ssd_*`, `shortconv_*`, `flash_*` and `moe_*` metrics match
    calls by prefix: the new calls begin with none of them."""
    from benchmarks.lib import flops_hybrid, flops_kda

    for name in ("gatenorm_fwd", "gatenorm_bwd"):
        assert flops_kda.kda_kernel_kind(name) is None
        assert flops_hybrid.ssd_kernel_kind(name) is None
        assert not name.startswith(("shortconv_", "flash_", "moe_", "hc_"))
    in_conv = loader.load_metric("shortconv_time_pct.train").in_conv
    assert not in_conv("layer_8/ssm/ssm.gate_norm/gatenorm_fwd")


def test_the_two_entries_are_declared_as_their_files_say():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, better in zip(NAMES, ("lower", "higher")):
        module, entry = loader.load_metric(name), entries[name]
        assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
            module.LAYER, module.UNIT, module.MOVES, module.SOURCE
        )
        assert entry["better"] == better
        assert entry["workloads"] == [NEMOTRON, KIMI]
    # appended behind PR 42's pair, in this order (a later PR appends more)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("shortconv_roofline.train")
    assert tuple(names[at + 1:at + 3]) == NAMES
