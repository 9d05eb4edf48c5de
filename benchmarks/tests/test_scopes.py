"""`lib/scopes.py` and the six readers on it, on hand-made `Op` tuples and a
hand-made table: shares add to 100, an instruction the table lacks lowers
`scope_named_pct.train` and nothing else, None where a component is absent;
and the six entries of `BENCHMARK.json` against their files."""

import collections

import pytest

from benchmarks.lib import loader, program_trace as pt, scopes

Scope = collections.namedtuple("Scope", "path phase kind mixed", defaults=((),))
NAMES = (
    "scope_named_pct.train", "recompute_time_pct.train",
    "dense_matmul_time_pct.train", "head_loss_time_pct.train",
    "router_time_pct.train", "optimizer_time_pct.train",
)
ALL_SIX = [
    "olmo-1b-cut.train-2k", "olmo-1b.train-2k-dp2tp2", "zaya1-8b-ep2.train-8k",
    "olmo-1b-cut.train-8k", "nemotron-3-super-tp2ep64.train-8k",
    "laguna-s-2.1-ep32.train-8k",
]
SPARSE = [ALL_SIX[2], ALL_SIX[4], ALL_SIX[5]]

# One step of 1000 ns, twice; every instruction runs once a step.
TABLE = {
    "fusion.1": Scope("embed", "forward", "elementwise"),                 # 20
    "fusion.2": Scope("layer_0/attn/wq", "forward", "matmul", ("layer_0/ln_attn",)),  # 100
    "flash_fwd_compact.3": Scope("layer_0/attn/attend/flash_fwd_compact", "forward", "kernel"),  # 80
    "fusion.4": Scope("layer_1/moe/moe.route", "forward", "matmul"),      # 30
    "fusion.5": Scope("layer_1/attn/attn.gate", "recompute", "elementwise"),  # 10
    "fusion.6": Scope("head/bsd,vd->bsv", "forward", "matmul"),           # 150
    "fusion.7": Scope("loss", "forward", "elementwise"),                  # 40
    "fusion.8": Scope("head/bsd,vd->bsv", "backward", "matmul", ("embed", "optimizer")),  # 160
    "fusion.9": Scope("layer_0/attn/wq", "recompute", "matmul"),          # 100
    "fusion.10": Scope("layer_0/attn/wq", "backward", "matmul", ("optimizer",)),  # 200
    "fusion.11": Scope("embed", "backward", "elementwise"),               # 30
    "fusion.12": Scope("optimizer", "update", "elementwise"),             # 50
    "copy.13": Scope("", "other", "copy"),                                # 20
}
DURATIONS = dict(zip(TABLE, (20, 100, 80, 30, 10, 150, 40, 160, 100, 200, 30, 50, 20)))
STEP = sum(DURATIONS.values())  # 990, and 10 idle


def _core(extra=()):
    ops, at = [], 0
    for step in range(2):
        at = step * 1000
        for name, ns in (*DURATIONS.items(), *extra):
            ops.append(pt.Op(name, "fusion", "", at, at + ns, pt.CORE_LINE))
            at += ns
    return ops


class _Reduced:
    def __init__(self, core):
        self.window_ns = (0, 2000)
        self.busy_ns = {0: sum(o.end - o.start for o in core)}
        self.module_events = {0: [("jit_train_step", 0, 990), ("jit_train_step", 1000, 1990)]}

    def main_module(self, device=0):
        return "jit_train_step"


@pytest.fixture
def program(monkeypatch):
    """A registered step whose table is `TABLE`, and a profile of `_core()`."""
    from kubeflow_tpu.train import profiling

    calls = []

    def table():
        calls.append(1)
        return TABLE

    monkeypatch.setattr(profiling, "_STEP_PROGRAMS", {"jit_train_step": table})
    monkeypatch.setattr(scopes, "_made", {})
    monkeypatch.setattr(scopes, "_device_bytes", lambda: 0)
    return calls


def _read_all(core, monkeypatch, cell="a-cell"):
    monkeypatch.setattr(
        pt, "of_cell", lambda c: pt.ProgramTrace({0: core}, [], [], {})
    )
    reduced = _Reduced(core)
    return {
        name: loader.load_metric(name).read(reduced, [], {"name": cell})
        for name in NAMES
    }


def test_join_adds_up_to_the_busy_time():
    scoped = scopes.join(_core(), TABLE)
    assert scoped.busy_ns == 2 * STEP == sum(scoped.by.values())
    assert scoped.joined_ns == 2 * STEP
    assert scoped.named_ns == 2 * (STEP - 20)
    assert scoped.optimizer_in_matmul_ns == 2 * (160 + 200)
    assert scoped.by[("attn/wq", "backward", "matmul")] == 400
    assert scoped.by[("unnamed", "other", "copy")] == 40
    shares = [scoped.share(lambda c, p, k, key=key: (c, p, k) == key)
              for key in scoped.by]
    assert sum(shares) == pytest.approx(100.0)


def test_the_six_readers_on_a_hand_made_table(program, monkeypatch, capsys):
    read = _read_all(_core(), monkeypatch)
    pct = lambda ns: pytest.approx(100.0 * ns / STEP)
    assert read == {
        "scope_named_pct.train": pct(STEP - 20),
        "recompute_time_pct.train": pct(10 + 100),
        "dense_matmul_time_pct.train": pct(100 + 30 + 100 + 200),
        "head_loss_time_pct.train": pct(150 + 40 + 160 + 30),
        "router_time_pct.train": pct(30 + 10),
        "optimizer_time_pct.train": pct(50),
    }
    assert len(program) == 1  # the table is made once a process
    out = capsys.readouterr().out
    assert out.count("[scopes]") == 1
    line = next(l for l in out.splitlines() if l.startswith("[scopes]"))
    assert "module=jit_train_step" in line and "steps=2.0 " in line
    assert "busy_ms=0.001 total_ms=0.001 joined_pct=100.0" in line
    assert "optimizer_in_matmul_ms" in line and "TABLE_OF_ANOTHER" not in line
    assert "['fusion.10', 'layer_0/attn/wq'," in line
    assert "'copy:copy': " not in line and "'fusion:copy': " in line


def test_an_instruction_the_table_lacks_lowers_the_named_share_only(
    program, monkeypatch, capsys,
):
    whole = _read_all(_core(), monkeypatch)
    monkeypatch.setattr(scopes, "_made", {})
    extra = [("fusion.99", 10)]  # the idle 10 ns of each step
    read = _read_all(_core(extra), monkeypatch)
    busy = STEP + 10
    assert read["scope_named_pct.train"] == pytest.approx(100.0 * (STEP - 20) / busy)
    for name in NAMES[1:]:  # the same nanoseconds over a busy time 10 longer
        assert read[name] * busy == pytest.approx(whole[name] * STEP)
    line = capsys.readouterr().out.splitlines()[-1]
    assert "TABLE_OF_ANOTHER_PROGRAM=True" in line  # joined 99.0 %


def test_none_where_a_component_is_absent(program, monkeypatch):
    """A dense model under `remat: none`: no `recompute` phase, no router."""
    from kubeflow_tpu.train import profiling

    dense = {
        n: s for n, s in TABLE.items()
        if s.phase != "recompute" and not scopes.in_router(s.path)
    }
    monkeypatch.setattr(
        profiling, "_STEP_PROGRAMS", {"jit_train_step": lambda: dense}
    )
    core = [o for o in _core() if o.name in dense]
    read = _read_all(core, monkeypatch)
    assert read["recompute_time_pct.train"] is None
    assert read["router_time_pct.train"] is None
    assert None not in [read[n] for n in NAMES if n not in (
        "recompute_time_pct.train", "router_time_pct.train")]


def test_no_registered_program_no_profile_or_no_table_gives_none(
    program, monkeypatch, capsys,
):
    from kubeflow_tpu.train import profiling

    nothing = dict.fromkeys(NAMES)
    monkeypatch.setattr(pt, "of_cell", lambda c: None)
    reduced = _Reduced(_core())
    assert {n: loader.load_metric(n).read(reduced, [], {"name": "no-profile"})
            for n in NAMES} == nothing
    monkeypatch.setattr(profiling, "_STEP_PROGRAMS", {})
    assert _read_all(_core(), monkeypatch, "no-program") == nothing

    def broken():
        raise RuntimeError("the step cannot be compiled here")

    monkeypatch.setattr(profiling, "_STEP_PROGRAMS", {"jit_train_step": broken})
    assert _read_all(_core(), monkeypatch, "no-table") == nothing
    assert "[scopes] no table of jit_train_step: RuntimeError" in capsys.readouterr().out
    # The parent's program has no registry at all.
    monkeypatch.delattr(profiling, "step_programs")
    assert _read_all(_core(), monkeypatch, "the-parent") == nothing


@pytest.mark.parametrize("path, comp", [
    ("layer_3/attn/wq", "attn/wq"), ("layer_12/moe/moe.route", "moe/moe.route"),
    ("ln_final", "ln_final"), ("layer_0", "layer"), ("", "unnamed"),
])
def test_a_component_is_a_path_without_its_layer(path, comp):
    assert scopes.component(path) == comp


def test_the_six_entries_are_declared_as_their_files_say():
    benchmark = loader.load_benchmark()
    declared = {m["name"]: m for m in benchmark["per_layer"]}
    assert [m["name"] for m in benchmark["per_layer"]][-6:] == list(NAMES)
    for name in NAMES:
        reader, entry = loader.load_metric(name), declared[name]
        assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
            reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE
        ) == ("model step", "%", "tokens_per_s_per_chip", "program_span")
        assert set(entry["workloads"]) <= set(ALL_SIX)
        assert entry["better"] == ("higher" if name == NAMES[0] else "lower")
        for cell in entry["workloads"]:
            listed = loader.load_cell(cell, benchmark)["per_layer"]
            assert name in [m["name"] for m in listed]
    assert declared["scope_named_pct.train"]["workloads"] == ALL_SIX
    for name in ("recompute_time_pct.train", "router_time_pct.train"):
        assert set(declared[name]["workloads"]) <= set(SPARSE)
