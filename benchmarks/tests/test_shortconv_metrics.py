"""`shortconv_time_pct.train` and `shortconv_roofline.train` (PR 42) on
hand-made `Op` tuples and a hand-made table of scopes: a number at PR 41's
kind of program (XLA's passes under `kda.conv` / `ssm.conv`) and at the
kernel pair's, None in a cell with no recurrent mixer;
`lib/flops_shortconv.py`'s bytes for the two cells by hand."""

import collections
import json
import pathlib

import pytest

from benchmarks.lib import flops_shortconv, loader, program_trace as pt, scopes

ROOT = pathlib.Path(__file__).resolve().parents[2]
KIMI = "kimi-linear-48b-a3b-ep32.train-8k"
NEMOTRON = "nemotron-3-super-tp2ep64.train-8k"
Scope = collections.namedtuple("Scope", "path phase kind mixed", defaults=((),))

# One step of 1000 ns, twice; every instruction runs once a step.
XLA = {  # PR 41's program: elementwise passes under the scope
    "fusion.1": (Scope("layer_1/kda/kda.proj/wq", "forward", "matmul"), 300),
    "fusion.2": (Scope("layer_1/kda/kda.conv", "forward", "elementwise"), 60),
    "fusion.3": (Scope("layer_1/kda/kda.conv", "recompute", "elementwise"), 50),
    "fusion.4": (Scope("layer_1/kda/kda.conv", "backward", "elementwise"), 90),
    "kda_fwd.5": (Scope("layer_1/kda/kda.scan/kda_fwd", "forward", "kernel"), 100),
    "fusion.6": (Scope("layer_0/ssm/ssm.conv", "backward", "elementwise"), 40),
    "fusion.7": (Scope("head/bsd,vd->bsv", "forward", "matmul"), 350),
}
KERNELS = {  # PR 42's: the pair, and what XLA does round it
    "fusion.1": XLA["fusion.1"],
    "shortconv_fwd.2": (
        Scope("layer_1/kda/kda.conv/shortconv_fwd", "forward", "kernel"), 15),
    "shortconv_bwd.3": (
        Scope("layer_1/kda/kda.conv/shortconv_bwd", "backward", "kernel"), 30),
    "fusion.4": (Scope("layer_1/kda/kda.conv", "backward", "elementwise"), 5),
    "kda_fwd.5": XLA["kda_fwd.5"],
    "shortconv_bwd.6": (
        Scope("layer_0/ssm/ssm.conv/shortconv_bwd", "backward", "kernel"), 10),
    "fusion.7": XLA["fusion.7"],
}


class _Reduced:
    def __init__(self, core):
        self.window_ns = (0, 2000)
        self.busy_ns = {0: sum(o.end - o.start for o in core)}
        self.module_events = {
            0: [("jit_train_step", 0, 990), ("jit_train_step", 1000, 1990)]
        }

    def main_module(self, device=0):
        return "jit_train_step"


def _read(program: dict, cell: dict, monkeypatch) -> dict:
    from kubeflow_tpu.train import profiling

    ops = [
        pt.Op(name, "fusion", "", step * 1000 + at, step * 1000 + at + ns,
              pt.CORE_LINE)
        for step in range(2)
        for at, (name, (_, ns)) in zip(range(0, 1000, 100), program.items())
    ]
    table = {name: scope for name, (scope, _) in program.items()}
    monkeypatch.setattr(profiling, "_STEP_PROGRAMS", {"jit_train_step": lambda: table})
    monkeypatch.setattr(scopes, "_made", {})
    monkeypatch.setattr(scopes, "_device_bytes", lambda: 0)
    monkeypatch.setattr(
        pt, "of_cell", lambda c: pt.ProgramTrace({0: ops}, [], [], {})
    )
    return {
        name: loader.load_metric(name).read(_Reduced(ops), [], cell)
        for name in ("shortconv_time_pct.train", "shortconv_roofline.train")
    }


def _cell(name: str) -> dict:
    cell = loader.load_cell(name, loader.load_benchmark(ROOT))
    numbers = cell["driver"].model_numbers(cell["config"])
    return {**cell, "facts": {"numbers": numbers, "device_kind": "TPU v5 lite"}}


def test_the_cells_bytes_by_hand():
    """Kimi: 4 delta layers x (q, k, v) x 5 arrays of [8192, 4096] at 2
    bytes, 4.03 GB a step; nemotron: 5 state-space layers x 5 arrays of
    [8192, 4096 + 2 x 4 x 128], 2.10 GB."""
    kimi, nemotron = _cell(KIMI), _cell(NEMOTRON)
    assert flops_shortconv.conv_layers(kimi["facts"]["numbers"]) == (4, 3 * 4096)
    assert flops_shortconv.conv_layers(nemotron["facts"]["numbers"]) == (5, 5120)
    assert flops_shortconv.shortconv_bytes(
        kimi["facts"]["numbers"], 8192
    ) == 4 * 15 * 8192 * 4096 * 2 == 4_026_531_840
    assert flops_shortconv.shortconv_bytes(
        nemotron["facts"]["numbers"], 8192
    ) == 5 * 5 * 8192 * 5120 * 2 == 2_097_152_000
    assert flops_shortconv.conv_layers({"hidden_size": 2048}) == (0, 0)
    assert flops_shortconv.shortconv_bytes({"hidden_size": 2048}, 8192) == 0.0


@pytest.mark.parametrize("program, under", [(XLA, 240), (KERNELS, 60)])
def test_a_number_at_both_kinds_of_program(program, under, monkeypatch):
    """Every phase under either scope, the kernels' events with them; the
    roofline by the cell's bytes over that time a step."""
    cell = _cell(KIMI)
    busy = sum(ns for _, ns in program.values())
    read = _read(program, cell, monkeypatch)
    assert read["shortconv_time_pct.train"] == pytest.approx(100.0 * under / busy)
    least = 4_026_531_840 / 819e9
    assert read["shortconv_roofline.train"] == pytest.approx(
        100.0 * least / (under * 1e-9)
    )


def test_none_without_a_recurrent_mixer_and_none_without_a_table(monkeypatch):
    plain = _cell("xing4.0-29b-a4b-ep8.train-8k")
    read = _read({"fusion.7": XLA["fusion.7"]}, plain, monkeypatch)
    assert read == {
        "shortconv_time_pct.train": None, "shortconv_roofline.train": None,
    }
    # a mixer's cell whose program registers no step: no table, None
    cell = _cell(NEMOTRON)
    _read(XLA, cell, monkeypatch)
    from kubeflow_tpu.train import profiling

    monkeypatch.setattr(profiling, "_STEP_PROGRAMS", {})
    monkeypatch.setattr(scopes, "_made", {})
    for name in ("shortconv_time_pct.train", "shortconv_roofline.train"):
        assert loader.load_metric(name).read(_Reduced([]), [], cell) is None
    in_conv = loader.load_metric("shortconv_time_pct.train").in_conv
    assert in_conv("kda/kda.conv") and in_conv("ssm/ssm.conv/shortconv_bwd")
    assert in_conv("shortconv_fwd")
    assert not in_conv("kda/kda.proj/wq") and not in_conv("cca.mix")


def test_the_two_entries_are_declared_as_their_files_say():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, better in (
        ("shortconv_time_pct.train", "lower"), ("shortconv_roofline.train", "higher"),
    ):
        module, entry = loader.load_metric(name), entries[name]
        assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
            module.LAYER, module.UNIT, module.MOVES, module.SOURCE
        )
        assert entry["better"] == better
        assert entry["workloads"] == [KIMI, NEMOTRON]
    assert [m["name"] for m in bench["per_layer"]][-2:] == list(entries)[-2:] == [
        "shortconv_time_pct.train", "shortconv_roofline.train",
    ]
