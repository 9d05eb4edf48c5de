"""`start_trace_lower_s.train`, `start_backend_s.train` and
`start_cache_misses.train` (PR 50) on a hand-made list of spans: two set-up
`train.fit` calls, the window's, and compiles after it; None from a ring
that dropped spans, from one with no `train.fit`, and from a program whose
ring has no undrained read. The live path on the CPU: the program's own ring
after three `fit()` calls."""

import json
import pathlib

import pytest

from benchmarks.lib import loader, start

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAMES = {
    "start_trace_lower_s.train": "s",
    "start_backend_s.train": "s",
    "start_cache_misses.train": "programs",
}
S = 1_000_000_000  # ns


def _span(name, span_id, parent, start_s, end_s, **attributes):
    return {
        "name": name, "spanId": span_id, "parentId": parent,
        "traceId": "t", "startNs": int(start_s * S), "endNs": int(end_s * S),
        "attributes": attributes,
    }


def _ring():
    """Oldest finished first, as the ring holds them. A state made outside
    any span; step 1 under the first `fit()`; a second `fit()` that loads a
    small program; the window, clean; the reference and the table of scopes
    after it."""
    return [
        # the trainer's construction: no parent
        _span("compile.trace", "c1", None, 1.0, 1.5, fun_name="seeded_state"),
        _span("compile.lower", "c2", None, 1.5, 1.75, fun_name="jit(seeded_state)"),
        _span("compile.backend", "c3", None, 1.75, 3.75,
              fun_name="jit(seeded_state)", cache="hit", retrieval_s=1.9,
              saved_s=20.0),
        # fit() one: step 1 builds the step, traced twice (the driver's own)
        _span("train.init", "i1", "f1", 4.0, 4.25),
        _span("compile.trace", "c4", "d1", 4.5, 8.5, fun_name="train_step"),
        _span("compile.lower", "c5", "d1", 8.5, 10.5, fun_name="jit(train_step)"),
        _span("compile.lower", "c6", "d1", 10.5, 12.0, fun_name="jit(train_step)"),
        _span("compile.backend", "c7", "d1", 12.0, 20.0,
              fun_name="jit(train_step)", cache="miss"),
        _span("train.dispatch", "d1", "s1", 4.5, 20.25),
        _span("train.step", "s1", "f1", 4.25, 20.5, step_num=1),
        _span("train.fit", "f1", None, 4.0, 21.0, total_steps=1),
        # between the calls: a norm of the driver's own, under a span of its own
        _span("compile.backend", "c8", "b1", 21.0, 21.5,
              fun_name="jit(norms)", cache="off"),
        _span("bench.norms", "b1", None, 21.0, 21.75),
        # fit() two: a small program under the readback
        _span("compile.trace", "c9", "r2", 22.0, 22.125, fun_name="_mean"),
        _span("compile.backend", "c10", "r2", 22.125, 22.25,
              fun_name="jit(_mean)", cache="miss"),
        _span("train.readback", "r2", "f2", 22.0, 22.5),
        _span("train.fit", "f2", None, 21.75, 23.0, total_steps=3),
        # the window
        _span("train.dispatch", "d3", "s3", 24.0, 24.5),
        _span("train.step", "s3", "f3", 24.0, 25.0, step_num=4),
        _span("train.fit", "f3", None, 23.5, 33.5, total_steps=40),
        # after it: the reference, and the step compiled again for its table
        _span("compile.trace", "c11", None, 34.0, 40.0, fun_name="follow"),
        _span("compile.backend", "c12", None, 40.0, 90.0,
              fun_name="jit(follow)", cache="miss"),
        _span("compile.backend", "c13", None, 90.0, 120.0,
              fun_name="jit(train_step)", cache="miss"),
    ]


def _metrics(monkeypatch, spans, dropped=0):
    from kubeflow_tpu.utils import tracing

    ring = tracing.Tracer()
    monkeypatch.setattr(ring, "snapshot", lambda: spans)
    ring.dropped = dropped
    monkeypatch.setattr(tracing, "tracer", ring)
    start.of_process.cache_clear()
    try:
        return {
            name: loader.load_metric(name).read(None, [], {}) for name in NAMES
        }
    finally:
        start.of_process.cache_clear()


def test_the_three_readers_on_a_hand_made_ring(monkeypatch, capsys):
    read = _metrics(monkeypatch, _ring())
    # trace 0.5 + 4.0 + 0.125, lower 0.25 + 2.0 + 1.5
    assert read["start_trace_lower_s.train"] == pytest.approx(8.375)
    # backend 2.0 + 8.0 + 0.5 + 0.125; the 80 s after the window are not in it
    assert read["start_backend_s.train"] == pytest.approx(10.625)
    # the step and `_mean`; the reference's and the table's come after
    assert read["start_cache_misses.train"] == 2
    lines = [
        line for line in capsys.readouterr().out.splitlines()
        if line.startswith("[start]")
    ]
    assert len(lines) == 1  # read once a process, for all three
    assert "in_window=0" in lines[0] and "dropped=0" in lines[0]


def test_the_start_line_splits_by_parent_and_names_the_heaviest():
    found, why = start.summarize(_ring(), 0)
    assert why == ""
    assert found.spans == 10 and found.in_window == 0 and found.dropped == 0
    # the reference's trace and compile and the table's: 6 + 50 + 30 s
    assert found.after_s == pytest.approx(86.0) and found.after_misses == 2
    # the two set-up calls: 17 s and 1.25 s
    assert found.fit_s == pytest.approx(18.25)
    assert found.under_train_s == pytest.approx({
        "compile.trace": 4.125, "compile.lower": 3.5, "compile.backend": 8.125,
    })
    # `bench.norms` is a span, but no `train.*` one
    assert found.outside_s == pytest.approx({
        "compile.trace": 0.5, "compile.lower": 0.25, "compile.backend": 2.5,
    })
    assert found.programs == [
        ["train_step", 4.0, 3.5, 8.0, "miss"],
        ["seeded_state", 0.5, 0.25, 2.0, "hit"],
        ["norms", 0.0, 0.0, 0.5, "off"],
        ["_mean", 0.125, 0.0, 0.125, "miss"],
    ]
    line = found.line()
    assert line.startswith("[start] trace_lower_s=8.375 backend_s=10.625 ")
    assert "cache_misses=2" in line and "fit_s=18.250" in line
    assert "under_train_s={'trace': 4.125, 'lower': 3.5, 'backend': 8.125}" in line


def test_only_the_eight_heaviest_programs_are_named():
    ring = [
        _span("compile.backend", f"c{i}", None, i, i + 0.01 * (i + 1),
              fun_name=f"jit(p{i})", cache="hit")
        for i in range(12)
    ] + [_span("train.fit", "f", None, 20.0, 30.0)]
    found, _ = start.summarize(ring, 0)
    assert [p[0] for p in found.programs] == [f"p{i}" for i in range(11, 3, -1)]
    assert found.backend_s == pytest.approx(0.01 * sum(range(1, 13)))


def test_a_compile_inside_the_window_is_counted_and_kept_out_of_the_start():
    ring = _ring()
    ring.insert(-5, _span(
        "compile.backend", "cw", "d3", 24.0, 24.25,
        fun_name="jit(train_step)", cache="miss",
    ))
    found, _ = start.summarize(ring, 0)
    assert found.in_window == 1
    assert found.backend_s == pytest.approx(10.625)
    assert found.cache_misses == 2


@pytest.mark.parametrize("fun_name, program", [
    ("train_step", "train_step"), ("jit(train_step)", "train_step"),
    ("pmap(step)", "step"), ("jit(<lambda>)", "<lambda>"),
    ("<lambda>", "<lambda>"), (None, "None"), ("a(b)(c)", "b)(c"),
])
def test_a_program_is_named_alike_in_every_phase(fun_name, program):
    assert start.program_of(fun_name) == program


def test_a_ring_that_dropped_spans_gives_none(monkeypatch, capsys):
    read = _metrics(monkeypatch, _ring(), dropped=3)
    assert read == dict.fromkeys(NAMES)
    assert "[start] none: the ring dropped 3 span(s)" in capsys.readouterr().out


def test_a_ring_without_a_fit_call_gives_none(monkeypatch, capsys):
    ring = [s for s in _ring() if s["name"] != "train.fit"]
    assert _metrics(monkeypatch, ring) == dict.fromkeys(NAMES)
    assert "[start] none: no train.fit span" in capsys.readouterr().out
    assert _metrics(monkeypatch, []) == dict.fromkeys(NAMES)


def test_a_program_without_the_undrained_read_gives_none(monkeypatch, capsys):
    """The parent commit's `Tracer`: `export()` alone."""
    from kubeflow_tpu.utils import tracing

    class Old:
        dropped = 0

        def export(self):
            raise AssertionError("the reader must not drain the ring")

    monkeypatch.setattr(tracing, "tracer", Old())
    start.of_process.cache_clear()
    try:
        for name in NAMES:
            assert loader.load_metric(name).read(None, [], {}) is None
    finally:
        start.of_process.cache_clear()
    assert capsys.readouterr().out.count("[start] none") == 1


def test_the_three_entries_are_declared_as_their_files_say():
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in benchmark["workloads"]]
    entries = {m["name"]: m for m in benchmark["per_layer"]}
    end_to_end = {m["name"] for m in benchmark["end_to_end"]}
    for name, unit in NAMES.items():
        entry, module = entries[name], loader.load_metric(name)
        assert entry == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": "train loop",
            "moves": "setup_s", "workloads": entry["workloads"],
        }
        assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"]
        )
        assert entry["moves"] in end_to_end
        # the ten cells this PR found, and only cells there are
        assert set(entry["workloads"]) <= set(cells)
        assert len(entry["workloads"]) >= 10
    # the three stand together, in this order, wherever later entries go
    names = [m["name"] for m in benchmark["per_layer"]]
    at = names.index("start_trace_lower_s.train")
    assert names[at:at + 3] == list(NAMES)
    # and they are what moves `setup_s`
    assert {
        m["name"] for m in benchmark["per_layer"] if m["moves"] == "setup_s"
    } >= set(NAMES)


def test_the_programs_own_ring_after_three_fit_calls(capsys):
    """The live path: set-up calls, a window that runs from the executable,
    a compile after it."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.testing.tinymodels import TinyMLP
    from kubeflow_tpu.train import SyntheticImages, TrainConfig, Trainer, fit
    from kubeflow_tpu.utils import tracing

    tracing.tracer.export()
    dropped = tracing.tracer.dropped
    tracing.tracer.dropped = 0
    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    config = TrainConfig(
        batch_size=8, learning_rate=0.05, warmup_steps=2, total_steps=24,
        fsdp_params=False, weight_decay=0.0,
    )

    class Held(Trainer):
        held = None

        def init_state(self, rng):
            if self.held is None:
                return super().init_state(rng)
            return self.held

    trainer = Held(TinyMLP(), config, mesh, example_input_shape=(2, 8, 8, 3))
    step = trainer.make_train_step()
    trainer.make_train_step = lambda: step
    data = SyntheticImages(mesh, 8, image_size=8, num_classes=10, seed=3)
    for total in (1, 3, 9):
        trainer.held = fit(
            trainer, data, total, log_every=1, handle_signals=False
        ).state
    jax.jit(lambda x: x * 11.0)(jnp.ones((3, 3)))  # after the window
    start.of_process.cache_clear()
    try:
        found = start.of_process()
        ring = tracing.tracer.snapshot()
    finally:
        start.of_process.cache_clear()
        tracing.tracer.dropped += dropped
    assert found is not None and found.in_window == 0 and found.dropped == 0
    assert found.programs[0][0] in ("train_step", "<lambda>", "make")
    assert "train_step" in [p[0] for p in found.programs]
    fits = [s for s in ring if s["name"] == "train.fit"]
    assert [s["attributes"]["total_steps"] for s in fits] == [1, 3, 9]
    before = [
        s for s in ring if s["name"] in start.PHASES
        and s["endNs"] <= fits[-1]["startNs"]
    ]
    assert found.spans == len(before) < len(
        [s for s in ring if s["name"] in start.PHASES]
    )
    assert found.trace_lower_s + found.backend_s == pytest.approx(sum(
        (s["endNs"] - s["startNs"]) / 1e9 for s in before
    ))
    assert found.fit_s == pytest.approx(sum(
        (s["endNs"] - s["startNs"]) / 1e9 for s in fits[:2]
    ))
    assert "[start] trace_lower_s=" in capsys.readouterr().out
