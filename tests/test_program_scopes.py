"""`train/profiling.program_scopes`: the step program's instructions joined
to the module path, phase and kind they came from, and the trainer's way to
the table (`Trainer.step_scopes`, `profiling.step_programs`, `Profiler`).

A text by hand pins the rules; a text recorded from the chip's compiler
(`tests/data/step_v5e_flash.hlo.txt`: two layers, remat `flash`, the flash
and rope kernels compiled) pins them on what a v5e's program looks like;
tiny steps compiled here, one a family of layer, pin that every scope the
program writes by hand reaches the table."""

import dataclasses
import functools
import gc
import json
import pathlib
import re

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.transformer import (
    AttentionKind, TransformerConfig, TransformerLM,
)
from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.train import TrainConfig, Trainer, fit, profiling
from kubeflow_tpu.train.profiling import (
    Profiler, ProfileSchedule, Scope, program_scopes, scope_of_op_name,
)

RECORDED = pathlib.Path(__file__).parent / "data" / "step_v5e_flash.hlo.txt"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

# -- the rules, on a text by hand ---------------------------------------------

BY_HAND = '''HloModule jit_train_step, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b), metadata={op_name="jit(train_step)/jvp(loss)/reduce_sum"}
}

%fused_gradient_and_update (p0: bf16[64,512], p1: bf16[64,256], p2: f32[512,256], p3: f32[512,256]) -> (f32[512,256], f32[512,256]) {
  %p0 = bf16[64,512]{1,0} parameter(0)
  %p1 = bf16[64,256]{1,0} parameter(1)
  %p2 = f32[512,256]{1,0} parameter(2)
  %p3 = f32[512,256]{1,0} parameter(3)
  %small = f32[64,256]{1,0} dot(%p1, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/layer_3/ln_mlp/dot_general"}
  %grad = f32[512,256]{1,0} dot(%p0, %p1), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/layer_3/mlp/wi_up/dot_general"}
  %mu = f32[512,256]{1,0} multiply(%grad, %p3), metadata={op_name="jit(train_step)/optimizer/mul"}
  %new = f32[512,256]{1,0} subtract(%p2, %mu), metadata={op_name="jit(train_step)/optimizer/sub"}
  ROOT %both = (f32[512,256]{1,0}, f32[512,256]{1,0}) tuple(%new, %mu)
}

%fused_norm (q0: bf16[64,512]) -> f32[64,512] {
  %q0 = bf16[64,512]{1,0} parameter(0)
  %wide = f32[64,512]{1,0} convert(%q0), metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/layer_3/ln_mlp/convert_element_type"}
  %sq = f32[64,512]{1,0} multiply(%wide, %wide), metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/layer_3/ln_mlp/mul"}
  %row = f32[64]{0} reduce(%sq, %q0), dimensions={1}, to_apply=%add, metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/layer_3/mlp/reduce_sum"}
  ROOT %out = f32[64,512]{1,0} multiply(%sq, %wide), metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/layer_3/ln_mlp/mul"}
}

%fused_relayout (r0: bf16[64,4,128]) -> bf16[64,512] {
  %r0 = bf16[64,4,128]{2,1,0} parameter(0)
  %turned = bf16[4,64,128]{2,1,0} transpose(%r0), dimensions={1,0,2}
  ROOT %flat = bf16[64,512]{1,0} bitcast(%turned)
}

%nested_scatter (n0: f32[64,512]) -> f32[64,512] {
  %n0 = f32[64,512]{1,0} parameter(0)
  ROOT %spread = f32[64,512]{1,0} add(%n0, %n0), metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/layer_3/moe/moe.route/add_any"}
}

%fused_outer (o0: f32[64,512]) -> f32[64,512] {
  %o0 = f32[64,512]{1,0} parameter(0)
  ROOT %inner.5 = f32[64,512]{1,0} fusion(%o0), kind=kLoop, calls=%nested_scatter
}

%loop_body (c: (s32[], f32[64,512])) -> (s32[], f32[64,512]) {
  %c = (s32[], f32[64,512]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %x = f32[64,512]{1,0} get-tuple-element(%c), index=1
  %in_loop = f32[64,512]{1,0} add(%x, %x), metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_0/ssm/ssm.scan/while/body/add"}
  ROOT %next = (s32[], f32[64,512]{1,0}) tuple(%i, %in_loop)
}

%loop_cond (c: (s32[], f32[64,512])) -> pred[] {
  %c.1 = (s32[], f32[64,512]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%c.1), index=0
  ROOT %go = pred[] compare(%i.1, %i.1), direction=LT
}

%async_gather (g0: f32[64,512]) -> f32[128,512] {
  %g0 = f32[64,512]{1,0} parameter(0)
  ROOT %all-gather.7 = f32[128,512]{1,0} all-gather(%g0), dimensions={0}, metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_0/mlp/wo/dot_general"}
}

ENTRY %main (tokens: bf16[64,512], w: f32[512,256], nu: f32[512,256]) -> f32[512,256] {
  %tokens = bf16[64,512]{1,0} parameter(0), metadata={op_name="batch['tokens']"}
  %w = f32[512,256]{1,0} parameter(1), metadata={op_name="state.params['layer_3']['mlp']['wi_up']['kernel']"}
  %nu = f32[512,256]{1,0} parameter(2)
  %heads = bf16[64,4,128]{2,1,0} bitcast(%tokens)
  %relayout.2 = bf16[64,512]{1,0} fusion(%heads), kind=kLoop, calls=%fused_relayout
  %copy-start.4 = (bf16[64,512]{1,0:S(1)}, bf16[64,512]{1,0}, u32[]) copy-start(%norm.1)
  %copy-done.4 = bf16[64,512]{1,0:S(1)} copy-done(%copy-start.4)
  %norm.1 = f32[64,512]{1,0} fusion(%tokens), kind=kLoop, calls=%fused_norm, metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/layer_3/ln_mlp/mul"}
  %outer.9 = f32[64,512]{1,0} fusion(%norm.1), kind=kLoop, calls=%fused_outer
  %flash_fwd_compact.8 = bf16[64,512]{1,0} custom-call(%tokens), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_3/attn/attend/jit(_flash_fwd_impl)/flash_fwd_compact/pallas_call"}
  %sort.1 = bf16[64,512]{1,0} custom-call(%tokens), custom_call_target="Sort", metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_3/moe/moe.route/sort"}
  %all-reduce.314 = f32[64,512]{1,0} all-reduce(%norm.1), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/layer_3/attn/wo/dot_general"}
  %all-gather-start.7 = ((f32[64,512]{1,0}), f32[128,512]{1,0}) async-start(%norm.1), calls=%async_gather
  %all-gather-done.7 = f32[128,512]{1,0} async-done(%all-gather-start.7)
  %loop = (s32[], f32[64,512]{1,0}) while(%norm.1), condition=%loop_cond, body=%loop_body, metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_0/ssm/ssm.scan/while"}
  %loss.3 = f32[] reduce(%norm.1, %nu), dimensions={0,1}, to_apply=%add, metadata={op_name="jit(train_step)/jvp(loss)/reduce_sum"}
  %guard.1 = pred[] compare(%loss.3, %loss.3), direction=EQ, metadata={op_name="jit(train_step)/guard/is_finite"}
  %fusion.20 = (f32[512,256]{1,0}, f32[512,256]{1,0}) fusion(%tokens, %tokens, %w, %nu), kind=kOutput, calls=%fused_gradient_and_update, metadata={op_name="jit(train_step)/optimizer/sub"}
  ROOT %result = f32[512,256]{1,0} get-tuple-element(%fusion.20), index=0
}
'''


@functools.cache
def by_hand():
    return program_scopes(BY_HAND, root="TransformerLM")


@pytest.mark.parametrize("name, want", [
    # A weight gradient with AdamW fused in: the heaviest `dot`'s path (not
    # the smaller one's, not the root's), and the others in `mixed`.
    ("fusion.20", Scope("layer_3/mlp/wi_up", "backward", "matmul",
                        ("layer_3/ln_mlp", "optimizer"))),
    # No matmul: the largest result's, of equals the one nearest the root.
    ("norm.1", Scope("layer_3/ln_mlp", "recompute", "elementwise",
                     ("layer_3/mlp",))),
    # A fusion that nests another reads the nested one's instructions.
    ("outer.9", Scope("layer_3/moe/moe.route", "backward", "elementwise")),
    ("flash_fwd_compact.8", Scope(
        "layer_3/attn/attend/flash_fwd_compact", "forward", "kernel")),
    ("sort.1", Scope("layer_3/moe/moe.route", "forward", "elementwise")),
    ("all-reduce.314", Scope("layer_3/attn/wo", "backward", "collective")),
    ("all-gather-start.7", Scope("", "other", "collective")),
    ("all-gather-done.7", Scope("", "other", "collective")),
    ("all-gather.7", Scope("layer_0/mlp/wo", "forward", "collective")),
    ("in_loop", Scope("layer_0/ssm/ssm.scan", "forward", "elementwise")),
    ("loss.3", Scope("loss", "forward", "elementwise")),
    ("guard.1", Scope("guard", "other", "elementwise")),
    # Relayouts the compiler added carry no name: they take what they move.
    ("copy-start.4", Scope("layer_3/ln_mlp", "recompute", "copy")),
    ("copy-done.4", Scope("layer_3/ln_mlp", "recompute", "copy")),
    ("relayout.2", Scope("", "other", "copy")),
    ("tokens", Scope("", "other", "elementwise")),
])
def test_by_hand(name, want):
    assert by_hand()[name] == want


def test_by_hand_covers_the_bodies_the_entry_calls_and_no_fused_one():
    names = set(by_hand())
    assert {"in_loop", "go", "all-gather.7", "result", "loop"} <= names
    # Inside a fused computation or a reduction nothing is an event.
    assert not names & {"grad", "mu", "small", "sum", "turned", "wide"}


# The recurrent mixers' short-convolution kernels as the chip's compiler
# names them in the kimi and the nemotron cell's steps (their op_names,
# operands cut): the forward call inside the mixer's own scope, the
# hand-written backward under the same scope by the rule's own stack.
SHORTCONV = '''HloModule jit_train_step, is_scheduled=true

ENTRY %main (u: bf16[1,8192,4096], t: f32[8,4096]) -> bf16[1,8192,4096] {
  %u = bf16[1,8192,4096]{2,1,0} parameter(0)
  %t = f32[8,4096]{1,0} parameter(1)
  %shortconv_fwd.7 = bf16[1,8192,4096]{2,1,0} custom-call(%u, %u, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_4/kda/kda.conv/jit(_fwd)/shortconv_fwd/pallas_call"}
  %shortconv_fwd.9 = bf16[1,8192,4096]{2,1,0} custom-call(%u, %u, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_8/ssm/ssm.conv/jit(_fwd)/shortconv_fwd/pallas_call"}
  %shortconv_bwd.14 = (bf16[1,8192,4096]{2,1,0}, f32[32,4096]{1,0}) custom-call(%u, %u, %u, %shortconv_fwd.7, %shortconv_fwd.7, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/layer_4/kda/kda.conv/jit(_bwd)/shortconv_bwd/pallas_call"}
  %shortconv_bwd.2 = (bf16[1,8192,4096]{2,1,0}, f32[40,4096]{1,0}) custom-call(%u, %u, %u, %shortconv_fwd.9, %shortconv_fwd.9, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/layer_8/ssm/ssm.conv/jit(_bwd)/shortconv_bwd/pallas_call"}
  %shortconv_fwd.11 = bf16[1,8192,4096]{2,1,0} custom-call(%u, %u, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/layer_4/kda/kda.conv/jit(_fwd)/shortconv_fwd/pallas_call"}
  ROOT %du = bf16[1,8192,4096]{2,1,0} get-tuple-element(%shortconv_bwd.14), index=0
}
'''


@pytest.mark.parametrize("name, want", [
    ("shortconv_fwd.7", ("layer_4/kda/kda.conv/shortconv_fwd", "forward")),
    ("shortconv_fwd.9", ("layer_8/ssm/ssm.conv/shortconv_fwd", "forward")),
    ("shortconv_bwd.14", ("layer_4/kda/kda.conv/shortconv_bwd", "backward")),
    ("shortconv_bwd.2", ("layer_8/ssm/ssm.conv/shortconv_bwd", "backward")),
    # what a plan that keeps neither projection nor result would run again
    ("shortconv_fwd.11", ("layer_4/kda/kda.conv/shortconv_fwd", "recompute")),
])
def test_the_short_convolution_kernels_sit_under_their_mixers_scopes(name, want):
    """Kind `kernel` under `kda.conv` / `ssm.conv`, forward and backward:
    what `kda_layer_time_pct.train`, `shortconv_time_pct.train` and the
    `[scopes]` line read them by."""
    scope = program_scopes(SHORTCONV, root="TransformerLM")[name]
    assert (scope.path, scope.phase, scope.kind) == (*want, "kernel")


# The gated-norm kernels as the chip's compiler names them in the nemotron
# and the kimi cell's steps (their op_names, operands cut).
GATENORM = '''HloModule jit_train_step, is_scheduled=true

ENTRY %main (y: bf16[1,8192,4096], t: f32[8,4096]) -> bf16[1,8192,4096] {
  %y = bf16[1,8192,4096]{2,1,0} parameter(0)
  %t = f32[8,4096]{1,0} parameter(1)
  %gatenorm_fwd.5 = bf16[1,8192,4096]{2,1,0} custom-call(%y, %y, %y, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_0/ssm/ssm.gate_norm/jit(_fwd)/gatenorm_fwd/pallas_call"}
  %gatenorm_fwd.2 = bf16[1,8192,4096]{2,1,0} custom-call(%y, %y, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(TransformerLM)/layer_4/kda/kda.gate_norm/jit(_fwd)/gatenorm_fwd/pallas_call"}
  %gatenorm_bwd.9 = (bf16[1,8192,4096]{2,1,0}, bf16[1,8192,4096]{2,1,0}, bf16[1,8192,4096]{2,1,0}, f32[16,4096]{1,0}) custom-call(%y, %y, %y, %gatenorm_fwd.5, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/layer_0/ssm/ssm.gate_norm/jit(_bwd)/gatenorm_bwd/pallas_call"}
  %gatenorm_bwd.3 = (bf16[1,8192,4096]{2,1,0}, bf16[1,8192,4096]{2,1,0}, f32[8,4096]{1,0}) custom-call(%y, %y, %gatenorm_fwd.2, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/layer_4/kda/kda.gate_norm/jit(_bwd)/gatenorm_bwd/pallas_call"}
  %gatenorm_fwd.11 = bf16[1,8192,4096]{2,1,0} custom-call(%y, %y, %t), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/rematted_computation/layer_4/kda/kda.gate_norm/jit(_fwd)/gatenorm_fwd/pallas_call"}
  ROOT %dy = bf16[1,8192,4096]{2,1,0} get-tuple-element(%gatenorm_bwd.9), index=0
}
'''


@pytest.mark.parametrize("name, want", [
    ("gatenorm_fwd.5", ("layer_0/ssm/ssm.gate_norm/gatenorm_fwd", "forward")),
    ("gatenorm_fwd.2", ("layer_4/kda/kda.gate_norm/gatenorm_fwd", "forward")),
    ("gatenorm_bwd.9", ("layer_0/ssm/ssm.gate_norm/gatenorm_bwd", "backward")),
    ("gatenorm_bwd.3", ("layer_4/kda/kda.gate_norm/gatenorm_bwd", "backward")),
    # what a plan that refuses `mixer_gated` would run again
    ("gatenorm_fwd.11", ("layer_4/kda/kda.gate_norm/gatenorm_fwd", "recompute")),
])
def test_the_gated_norm_kernels_sit_under_their_mixers_scopes(name, want):
    """Kind `kernel` under `ssm.gate_norm` / `kda.gate_norm`, forward and
    backward: what `kda_layer_time_pct.train`, `gatenorm_time_pct.train`
    and the `[scopes]` line read them by."""
    scope = program_scopes(GATENORM, root="TransformerLM")[name]
    assert (scope.path, scope.phase, scope.kind) == (*want, "kernel")


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/jvp(TransformerLM)/layer_1/moe/moe.route/dot_general",
     ("layer_1/moe/moe.route", "forward")),
    ("jit(train_step)/transpose(jvp(TransformerLM))/ln_final/mul",
     ("ln_final", "backward")),
    # A `checkpoint` frame alone is the backward OF a checkpointed block.
    ("jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/"
     "checkpoint/layer_0/attn/wq/dot_general", ("layer_0/attn/wq", "backward")),
    ("jit(train_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/"
     "checkpoint/rematted_computation/layer_0/attn/wq/dot_general",
     ("layer_0/attn/wq", "recompute")),
    ("jit(train_step)/jvp(loss)/jit(take_along_axis)/gather", ("loss", "forward")),
    ("jit(train_step)/transpose(jvp(loss))", ("loss", "backward")),
    ("jit(train_step)/jvp()/reduce_max", ("", "forward")),
    ("jit(train_step)/optimizer/jit(_where)/select_n", ("optimizer", "update")),
    ("jit(train_step)/jvp(TransformerLM)/head/bsd,vd->bsv/dot_general",
     ("head/bsd,vd->bsv", "forward")),
    ("jit(train_step)/jvp(TransformerLM)/embed/gather;"
     "jit(train_step)/jvp(TransformerLM)/layer_0/ln_attn/mul", ("embed", "forward")),
    # A function lowered once keeps its first stack behind each call's own.
    ("jit(train_step)/jvp(TransformerLM)/layer_7/moe/jit(searchsorted)/"
     "jit(train_step)/jvp(TransformerLM)/layer_0/moe/jit(searchsorted)/"
     "vmap()/closed_call/while/body/closed_call/gather",
     ("layer_7/moe", "forward")),
    # A checkpoint inside a named module (the multi-token module's block)
    # traces its body from the root again: the module's frame stands once.
    ("jit(train_step)/jvp(TransformerLM)/mtp/block/attn/wo/dot_general",
     ("mtp/block/attn/wo", "forward")),
    ("jit(train_step)/transpose(jvp(TransformerLM))/mtp/jvp(TransformerLM)/mtp/"
     "checkpoint/rematted_computation/block/attn/wo/dot_general",
     ("mtp/block/attn/wo", "recompute")),
    ("jit(train_step)/transpose(jvp(TransformerLM))/mtp/jvp(TransformerLM)/mtp/"
     "checkpoint/block/moe/moe.route/mul", ("mtp/block/moe/moe.route", "backward")),
    ("jit(train_step)/transpose(jvp(TransformerLM))/mtp/mtp.loss/sub",
     ("mtp/mtp.loss", "backward")),
    ("state.params['layer_0']['attn']['wq']['kernel']", ("", "other")),
    ("reduce_sum", ("", "other")),
    ("", ("", "other")),
])
def test_scope_of_op_name(op_name, want):
    assert scope_of_op_name(op_name, root="TransformerLM") == want


# -- a program of the chip's compiler -------------------------------------------


@functools.cache
def recorded():
    text = RECORDED.read_text()
    return text, program_scopes(text, root="TransformerLM")


def _entry_instructions(text: str) -> list[str]:
    """The entry computation's instruction names, read apart from
    `program_scopes`' own parser."""
    body = text[text.index("\nENTRY "):]
    body = body[: body.index("\n}")]
    return re.findall(r"^\s+(?:ROOT )?%?(\S+) = ", body, re.M)


def test_recorded_every_entry_instruction_has_a_scope():
    text, table = recorded()
    entry = _entry_instructions(text)
    assert len(entry) > 500 and set(entry) <= set(table)
    assert all(s.phase in profiling.PHASES and s.kind in profiling.KINDS
               for s in table.values())


def test_recorded_kernels_are_placed_by_layer_and_phase():
    _, table = recorded()
    kernels = {n: s for n, s in table.items() if s.kind == "kernel"}
    by_path = {}
    for s in kernels.values():
        by_path.setdefault(re.sub(r"layer_\d", "layer_N", s.path), set()).add(s.phase)
    assert by_path == {
        "layer_N/attn/attend/flash_fwd_compact": {"forward"},  # not run again
        "layer_N/attn/attend/flash_bwd_fused": {"backward"},
        "layer_N/attn/attend/flash_delta": {"backward"},
        "layer_N/attn/rope/rope_turn_fwd": {"forward", "recompute"},
        "layer_N/attn/rope/rope_turn_bwd": {"backward"},
    }
    assert all(n.split(".")[0] == s.path.split("/")[-1] for n, s in kernels.items())


def test_recorded_weight_gradients_carry_the_update_as_mixed():
    _, table = recorded()
    fused = [s for s in table.values()
             if s.kind == "matmul" and "optimizer" in s.mixed]
    assert fused and all(s.phase == "backward" for s in fused)
    assert {s.path for s in fused} >= {
        f"layer_{i}/mlp/{w}" for i in (0, 1) for w in ("wi_gate", "wi_up", "wo")
    }
    # A projection's matmul is there four times: forward, forward again,
    # and two backward (the input's and the weight's gradients).
    phases = [s.phase for s in table.values()
              if s.kind == "matmul" and s.path == "layer_1/mlp/wi_gate"]
    assert sorted(phases) == ["backward", "backward", "forward", "recompute"]


# -- steps compiled here -----------------------------------------------------------

BASE = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=64)
EXPERTS = dict(
    d_ff=16, num_experts=8, experts_held=(0, 4), experts_per_token=2,
    router="sigmoid", routed_scaling=2.5, tie_embeddings=False,
)
MODELS = {
    "dense": TransformerConfig(**BASE),
    "cca_experts": TransformerConfig(**{
        **BASE, "n_kv_heads": 2, "d_ff": 16, "rope_fraction": 0.5, "cca": True,
        "num_experts": 4, "experts_held": (0, 2), "router_hidden": 16,
        "router_force_balance": True,
    }),
    "state_space": TransformerConfig(**{
        **BASE, **EXPERTS, "n_layers": 3, "n_kv_heads": 1, "rope_fraction": 0.0,
        "mlp_act": "relu2", "moe_latent": 16, "moe_shared_ff": 32,
        "layer_pattern": "ME*", "ssm_heads": 4, "ssm_head_dim": 8,
        "ssm_state": 16, "ssm_groups": 2, "ssm_chunk": 8,
    }),
    "window_gate": TransformerConfig(**{
        **BASE, **EXPERTS, "n_layers": 3, "n_kv_heads": 2, "moe_shared_ff": 16,
        "attention_kinds": (
            AttentionKind(4, None, 1e4, 0.5), AttentionKind(6, 8, 1e4, 1.0),
        ),
        "attention_pattern": (0, 1, 1), "attention_gate": True,
        "dense_layers": 1, "dense_d_ff": 64,
    }),
}
# Every scope the program writes by hand, by the model that runs it.
SCOPES = {
    "dense": {"embed", "head", "loss", "optimizer", "rope", "attend"},
    "cca_experts": {
        "embed", "head", "loss", "optimizer", "rope", "cca.mix", "cca.attend",
        "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
    },
    "state_space": {
        "embed", "head", "loss", "optimizer", "attend", "moe.route",
        "moe.dispatch", "moe.experts", "moe.combine", "moe.latent_in",
        "moe.shared", "moe.latent_out", "ssm.in_proj", "ssm.conv", "ssm.scan",
        "ssm.gate_norm", "ssm.out_proj",
    },
    "window_gate": {
        "embed", "head", "loss", "optimizer", "rope", "attend.full",
        "attend.window", "attn.gate", "moe.route", "moe.dispatch",
        "moe.experts", "moe.combine", "moe.shared",
    },
}
CASES = [("dense", "none"), ("dense", "flash"), ("cca_experts", "flash"),
         ("state_space", "flash"), ("window_gate", "flash")]
TOKENS = jax.ShapeDtypeStruct((2, 16), jnp.int32)


def _trainer(cfg, cls=Trainer, guard=None):
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    config = TrainConfig(
        batch_size=2, optimizer="adamw", label_smoothing=0.0,
        fsdp_params=False, train_metrics="loss", warmup_steps=2, total_steps=10,
    )
    return cls(
        TransformerLM(cfg, mesh=mesh), config, mesh,
        example_input_shape=(2, 16), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels", guard=guard,
    )


@functools.cache
def compiled_here(model: str, remat: str):
    trainer = _trainer(dataclasses.replace(MODELS[model], remat_policy=remat))
    table = trainer.step_scopes({"tokens": TOKENS, "labels": TOKENS})
    return trainer._step_program.text(), table


@pytest.mark.parametrize("model, remat", CASES)
def test_compiled_here_every_entry_instruction_has_a_scope(model, remat):
    text, table = compiled_here(model, remat)
    assert set(_entry_instructions(text)) <= set(table)


@pytest.mark.parametrize("model, remat", CASES)
def test_compiled_here_recompute_only_under_remat(model, remat):
    _, table = compiled_here(model, remat)
    phases = {s.phase for s in table.values()}
    assert {"forward", "backward", "update", "other"} <= phases
    assert ("recompute" in phases) == (remat == "flash")


@pytest.mark.parametrize("model, remat", CASES)
def test_compiled_here_every_scope_by_hand_reaches_the_table(model, remat):
    _, table = compiled_here(model, remat)
    frames = set()
    for scope in table.values():
        for path in (scope.path, *scope.mixed):
            frames.update(path.split("/"))
    assert SCOPES[model] <= frames
    # The root module's name is taken off, no transformation is left on.
    assert "TransformerLM" not in frames
    assert not [f for f in frames if "(" in f or f in ("checkpoint", "jit")]


def test_compiled_here_the_guards_screen_has_its_scope():
    from kubeflow_tpu.train.guard import AnomalyGuard

    trainer = _trainer(MODELS["dense"], guard=AnomalyGuard())
    table = trainer.step_scopes({"tokens": TOKENS, "labels": TOKENS})
    paths = {s.path.split("/")[0] for s in table.values()}
    assert {"guard", "optimizer", "loss"} <= paths


# -- the trainer's way to the table ----------------------------------------------


class _Lowerings:
    """Programs lowered while it is open (as `benchmarks/run.py` counts)."""

    count = 0
    open = False

    def __call__(self, event, duration, **kwargs):
        if self.open and event == LOWERING_EVENT:
            self.count += 1


@pytest.fixture(scope="module")
def lowerings():
    counter = _Lowerings()
    jax.monitoring.register_event_duration_secs_listener(counter)
    return counter


class _HeldTrainer(Trainer):
    """As the benchmark's drivers hold a trainer: `fit()` gets a plain
    function round the jitted step, which is made once."""

    _step = None
    calls = 0

    def make_train_step(self):
        if self._step is None:
            jitted = super().make_train_step()

            def step(state, batch):
                self.calls += 1
                return jitted(state, batch)

            self._step = step
        return self._step


def _batches(trainer, n=3):
    tokens = jax.device_put(
        np.arange(32, dtype=np.int32).reshape(2, 16) % 64,
        trainer.batch_sharding(2),
    )
    return [{"tokens": tokens, "labels": tokens}] * n


def test_making_and_fitting_lower_nothing_more_and_keep_no_array(
    lowerings, monkeypatch,
):
    cfg = dataclasses.replace(MODELS["dense"], remat_policy="flash", d_ff=48)
    # The table may not be made unless somebody asks.
    monkeypatch.setattr(
        profiling.StepProgram, "text",
        lambda self: pytest.fail("fit() lowered the step for its table"),
    )

    def run(cls):
        jax.clear_caches()
        trainer = _trainer(cfg, cls)
        trainer.state_shardings()  # draws a key: small programs of its own
        lowerings.count, lowerings.open = 0, True
        try:
            trainer.make_train_step()
            made = lowerings.count
            result = fit(trainer, _batches(trainer), 3, handle_signals=False)
            return trainer, result, made, lowerings.count
        finally:
            lowerings.open = False

    class _Unhooked(_HeldTrainer):  # the parent's trainer: nothing noted
        def note_step_arguments(self, state, batch):
            pass

    _, _, made_parent, fitted_parent = run(_Unhooked)
    trainer, result, made, fitted = run(_HeldTrainer)
    assert made == made_parent == 0
    assert fitted == fitted_parent
    assert trainer.calls == 3 and result.steps_done == 3

    program = profiling.step_programs()["jit_train_step"]
    assert program is trainer._step_program
    kept = jax.tree_util.tree_leaves(
        [v for k, v in vars(program).items() if k != "jitted"]
    )
    assert kept and not [x for x in kept if isinstance(x, jax.Array)]
    state, batch = program.arguments
    assert state.params["layer_0"]["mlp"]["wo"]["kernel"].shape == (48, 32)
    assert batch["tokens"].shape == (2, 16)
    # Nothing of the trainer's is reachable from the registry's entry.
    del result
    gc.collect()
    assert not [
        r for r in gc.get_referrers(trainer) if r is program or r is vars(program)
    ]


def test_step_scopes_after_fit_through_a_wrapped_step(lowerings):
    cfg = dataclasses.replace(MODELS["dense"], remat_policy="flash", d_ff=80)
    trainer = _trainer(cfg, _HeldTrainer)
    result = fit(trainer, _batches(trainer), 3, handle_signals=False)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        (result.state, _batches(trainer, 1)[0]),
    )
    del result
    lowerings.count, lowerings.open = 0, True
    try:
        table = trainer.step_scopes()
        assert trainer.step_scopes() is table  # kept
    finally:
        lowerings.open = False
    # On demand, and once at most: the lowering itself is still cached
    # where nobody cleared the caches (the benchmark's drivers do).
    assert lowerings.count <= 1
    assert trainer._step_program.arguments == abstract
    text = trainer._step_program.jitted.lower(*abstract).compile().as_text()
    names = set(re.findall(r"^\s+(?:ROOT )?%?(\S+) = ", text, re.M))
    assert set(_entry_instructions(text)) <= set(table) <= names
    assert profiling.step_programs()["jit_train_step"]() is table


def test_step_scopes_wants_a_batch_before_the_first_step():
    trainer = _trainer(MODELS["dense"])
    with pytest.raises(ValueError, match="no arguments noted"):
        trainer.step_scopes()


def test_profiler_writes_the_table_beside_the_profile(tmp_path):
    cfg = dataclasses.replace(MODELS["dense"], remat_policy="none", d_ff=96)
    trainer = _trainer(cfg)
    profiler = Profiler(tmp_path, ProfileSchedule(start_step=1, num_steps=1))
    fit(trainer, _batches(trainer), 3, handle_signals=False, profiler=profiler)
    assert profiler.trace_written
    tables = json.loads((tmp_path / "step_scopes.json").read_text())
    table = tables["jit_train_step"]
    assert set(table) == set(trainer.step_scopes())
    some = next(v for v in table.values() if v["path"] == "layer_0/mlp/wo")
    assert set(some) == {"path", "phase", "kind", "mixed"}
    assert not any(v["phase"] == "recompute" for v in table.values())
