"""`lib/program_trace.py` and the readers on it, on hand-made intervals:
overlapped and exposed collectives, start/done pairs, a gap under two
spans; and on a profile written here (the host's `train.*` events)."""

import pytest

from benchmarks.lib import loader, program_trace as pt, trace

MESH = {"pp": 1, "dp": 2, "fsdp": 1, "sp": 1, "ep": 1, "tp": 2}
TP, DP = "[2,2]<=[4]", "[2,2]<=[2,2]T(1,0)"


def op(name, opcode, start, end, rest="", line=pt.CORE_LINE):
    return pt.Op(name, opcode, f"f32[8]{{0:T(8)}} {opcode}({rest})", start, end, line)


def test_opcode_is_the_first_lower_case_word_before_a_bracket():
    text = (
        "(bf16[25152,2048]{1,0:T(8,128)(2,1)}, f32[2048]{0:T(1024)S(1)}) "
        "all-reduce(bf16[25152,2048]{1,0:T(8,128)(2,1)} %fusion.7), "
        "channel_id=16, replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add"
    )
    assert pt.opcode_of(text) == "all-reduce"
    assert pt.groups_text(text) == DP
    assert pt.opcode_of("f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop") == "fusion"
    assert pt.opcode_of("no instruction") == ""
    assert pt.groups_text("f32[4]{0} fusion(f32[4]{0} %p)") == ""


@pytest.mark.parametrize("groups,expanded,axes", [
    (TP, [[0, 1], [2, 3]], "tp"),
    (DP, [[0, 2], [1, 3]], "dp"),
    ("{{0,2},{1,3}}", [[0, 2], [1, 3]], "dp"),
    ("{{0,1},{1,0}}", [[0, 1], [1, 0]], "tp"),   # source_target_pairs
    ("[1,4]<=[4]", [[0, 1, 2, 3]], "dp+tp"),
    ("{}", [[0, 1, 2, 3]], "dp+tp"),
    ("{{0},{1},{2},{3}}", [[0], [1], [2], [3]], "none"),
])
def test_replica_groups_name_the_mesh_axes_they_run_along(groups, expanded, axes):
    assert pt.expand_groups(groups, 4) == expanded
    assert pt.axes_of(groups, MESH) == axes


def test_subtract_keeps_what_the_cover_leaves():
    cover = [(10, 20), (30, 40)]
    assert pt.subtract([(0, 50)], cover) == [(0, 10), (20, 30), (40, 50)]
    assert pt.subtract([(12, 18)], cover) == []
    assert pt.subtract([(15, 35)], cover) == [(20, 30)]
    assert pt.subtract([(0, 5), (45, 50)], cover) == [(0, 5), (45, 50)]
    assert pt.subtract([(0, 50)], []) == [(0, 50)]


def test_collectives_exposed_overlapped_and_start_done_pairs():
    ops = [
        op("fusion.1", "fusion", 0, 100),
        # synchronous on the core's line: nothing runs beside it, all exposed
        op("all-reduce.1", "all-reduce", 100, 160, f"%fusion.1), replica_groups={TP}"),
        # an asynchronous pair, 200..400, hidden behind fusion.2 for 150 of it
        op("all-gather-start.2", "all-gather-start", 200, 201,
           f"%fusion.1), replica_groups={DP}"),
        op("fusion.2", "fusion", 210, 360),
        op("all-gather-done.2", "all-gather-done", 399, 400, "%all-gather-start.2"),
        # wrapped in async-start/-done: the name carries the opcode
        op("reduce-scatter-start.3", "async-start", 500, 501,
           f"%fusion.2), replica_groups={DP}"),
        op("reduce-scatter-done.3", "async-done", 520, 530, "%reduce-scatter-start.3"),
        # not collectives: a copy's pair, and a done whose start the trace cut
        op("copy-start.4", "copy-start", 540, 541),
        op("copy-done.4", "copy-done", 545, 546, "%copy-start.4"),
        op("all-reduce-done.9", "all-reduce-done", 600, 601, "%all-reduce-start.9"),
        # the other line is no cover and no collective source
        op("slice-start.5", "async-start", 0, 700, line="Async XLA Ops"),
    ]
    core = [o for o in ops if o.line == pt.CORE_LINE]
    found, cover = pt.collectives(core)
    assert [(c.opcode, c.groups, c.start, c.end) for c in found] == [
        ("all-reduce", TP, 100, 160),
        ("all-gather", DP, 200, 400),
        ("reduce-scatter", DP, 500, 530),
    ]
    assert cover == [(0, 100), (210, 360), (540, 541), (545, 546)]
    assert pt.exposed_ns(found, cover) == [60, 50, 30]
    assert pt.collective_table(core, MESH) == {
        ("all-reduce", "tp"): {"n": 1, "time_ns": 60, "exposed_ns": 60},
        ("all-gather", "dp"): {"n": 1, "time_ns": 200, "exposed_ns": 50},
        ("reduce-scatter", "dp"): {"n": 1, "time_ns": 30, "exposed_ns": 30},
    }


def test_loop_stall_splits_a_gap_under_two_spans():
    busy = [(0, 100), (140, 300), (310, 400)]
    window = (0, 420)            # idle: 100..140, 300..310, 400..420
    spans = [
        pt.Span("train.step", 0, 420, {}),
        pt.Span("train.data", 90, 120, {}),       # 20 of the first gap
        pt.Span("train.readback", 120, 150, {}),  # the other 20 of it
        pt.Span("train.dispatch", 295, 315, {}),  # not a stall span
        pt.Span("train.save", 390, 500, {}),      # cut by the window: 20
    ]
    names = ("train.data", "train.readback", "train.save")
    assert pt.loop_stall(busy, window, spans, names) == {
        "train.data": 20, "train.readback": 20, "train.save": 20,
    }
    assert pt.loop_stall(busy, window, [], names) == dict.fromkeys(names, 0)


class _Reduced:
    """What the readers use of `lib/trace.ReducedTrace`."""

    def __init__(self, core_ops, modules):
        self.window_ns = (
            min(o.start for o in core_ops), max(o.end for o in core_ops)
        )
        self.busy_ns = {0: pt.total(pt.union([(o.start, o.end) for o in core_ops]))}
        self.module_events = {0: modules}

    def main_module(self, device=0):
        return "jit_train_step"

    def idle_share(self, device=0):
        return 1 - self.busy_ns[0] / (self.window_ns[1] - self.window_ns[0])


def _cell():
    return {"name": "a-cell", "workload": {"mesh": {"dp": 2, "tp": 2}}}


def _read(name, reduced, cell=None):
    return loader.load_metric(name).read(reduced, [], cell or _cell())


def test_readers_on_a_hand_made_profile(monkeypatch, capsys):
    core = [
        op("fusion.1", "fusion", 0, 400),
        op("all-reduce.1", "all-reduce", 400, 500, f"%fusion.1), replica_groups={TP}"),
        op("all-reduce-start.2", "all-reduce-start", 500, 501,
           f"%fusion.1), replica_groups={DP}"),
        op("fusion.2", "fusion", 501, 900),
        op("all-reduce-done.2", "all-reduce-done", 900, 950, "%all-reduce-start.2"),
        op("fusion.3", "fusion", 1000, 2000),     # idle 950..1000
    ]
    profile = pt.ProgramTrace(
        {0: core},
        [pt.Span("train.data", 940, 980, {}), pt.Span("train.dispatch", 980, 2000, {})],
        [pt.Launch(7, 75, 100, 110)],
        {0: [pt.Execution("jit_train_step", 7, 75, 1000, 2000)]},
    )
    monkeypatch.setattr(pt, "of_cell", lambda cell: profile)
    reduced = _Reduced(core, [
        ("jit_train_step", 0, 950), ("jit_train_step", 1000, 2000),
    ])
    busy = 1950
    assert _read("collective_time_pct.train", reduced) == pytest.approx(
        100 * (100 + 450) / busy
    )
    # the pair's 450 less fusion.2's 399 (and its own markers are no cover)
    assert _read("collective_exposed_pct.train", reduced) == pytest.approx(
        100 * (100 + 51) / busy
    )
    stall = _read("loop_stall_pct.train", reduced)
    assert stall == pytest.approx(100 * 30 / 2000)
    assert stall <= 100 * reduced.idle_share()
    out = capsys.readouterr().out
    assert "[collectives] steps=2.00" in out
    assert "'all-reduce dp': {'n': 0.5, 'ms': 0.0, 'exposed_ms': 0.0}" in out
    assert "[loop_stall]" in out and "'train.data': 0.0" in out
    assert "enqueue_to_start_ms=[0.001]" in out  # 1000 - 110 ns


def test_readers_return_none_where_there_is_nothing_to_read(monkeypatch):
    """The parent's program writes no `train.*` span, a one-chip cell runs
    no collective, a test has no profile: None, not an error."""
    core = [op("fusion.1", "fusion", 0, 400)]
    reduced = _Reduced(core, [])
    names = (
        "collective_time_pct.train", "collective_exposed_pct.train",
        "loop_stall_pct.train",
    )
    for profile in (None, pt.ProgramTrace({0: core}, [], [], {})):
        monkeypatch.setattr(pt, "of_cell", lambda cell, p=profile: p)
        assert [_read(n, reduced) for n in names] == [None, None, None]
    monkeypatch.undo()
    assert pt.of_cell({"name": "no-such-cell"}) is None


def test_the_new_metrics_are_declared_as_their_files_say():
    declared = {m["name"]: m for m in loader.load_benchmark()["per_layer"]}
    cells = {
        "collective_time_pct.train": ["olmo-1b.train-2k-dp2tp2"],
        "collective_exposed_pct.train": ["olmo-1b.train-2k-dp2tp2"],
        "loop_stall_pct.train": [
            "olmo-1b-cut.train-2k", "olmo-1b.train-2k-dp2tp2",
        ],
    }
    for name, workloads in cells.items():
        reader, entry = loader.load_metric(name), declared[name]
        assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
            reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE
        )
        assert entry["workloads"] == workloads and entry["better"] == "lower"


def test_fit_spans_are_read_from_a_profile_written_here(tmp_path):
    """(ii): the host plane's `train.*` events with their attributes, from
    a profile of a tiny `fit()` on the CPU; no device plane, no launch."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.testing.tinymodels import TinyMLP
    from kubeflow_tpu.train import SyntheticImages, TrainConfig, Trainer, fit

    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    config = TrainConfig(batch_size=8, warmup_steps=1, total_steps=4,
                         fsdp_params=False)
    trainer = Trainer(TinyMLP(), config, mesh, example_input_shape=(2, 8, 8, 3))
    data = SyntheticImages(mesh, 8, image_size=8, num_classes=10,
                           dtype=jnp.float32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        fit(trainer, data, total_steps=3, log_every=100, handle_signals=False)
    finally:
        jax.profiler.stop_trace()
    profile = pt.load(trace.find_xplane(str(tmp_path)))
    names = [s.name for s in profile.spans]
    assert names.count("train.step") == 3
    assert names.count("train.data") == names.count("train.dispatch") == 3
    assert names.count("train.readback") == 1 and names.count("train.init") == 2
    steps = [s for s in profile.spans if s.name == "train.step"]
    assert [int(s.attributes["step_num"]) for s in steps] == [1, 2, 3]
    assert profile.ops == {} and profile.core_ops(0) == []
    assert profile.linked(0) == []
