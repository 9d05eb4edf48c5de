"""`remat_policy="flash"` keeps named results inside a byte budget
(`models/transformer.remat_plan`): the plan at the benchmark's three
sparse cells' shapes, the budgets at which it admits nothing, that every
trace of a trainer's step and every process of a job reads one plan,
per-device bytes on a mesh, nothing stated under accumulation, and from a small
model's gradient that a result kept is not formed again.

The cells' configurations come from the benchmark's files (as
`tests/test_nemotron.py` imports `benchmarks.*`): run from the repo root.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import transformer
from kubeflow_tpu.models.transformer import (
    KERNEL_RESULTS,
    SAVED_RESULTS,
    RematPlan,
    TransformerConfig,
    TransformerLM,
    remat_plan,
)
from kubeflow_tpu.ops import gatenorm, shortconv
from kubeflow_tpu.ops import streams as streams_ops
from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.testing.hlo import _walk_eqns, jaxpr_kernel_names
from kubeflow_tpu.train import TrainConfig, Trainer, fit
from kubeflow_tpu.utils import memory
from kubeflow_tpu.utils.memory import StepMemory

# `bytes_limit` of a v5e's allocator (my chip runs, PR 38: PERF.md §6).
V5E_LIMIT = 16_909_336_064


def _cell(name: str):
    """(configuration, tokens a step, the trainer) of a benchmark cell,
    built as its driver builds it, on the CPU."""
    from benchmarks.lib import loader

    cell = loader.load_cell(name, loader.load_benchmark())
    driver, work = cell["driver"], cell["workload"]
    numbers = driver.model_numbers(cell["config"])
    numbers["router_force_balance"] = bool(work.get("router_force_balance"))
    cfg = driver.transformer_config(
        numbers, attention_impl=work["attention_impl"],
        remat_policy=work["remat"],
    )
    mesh = build_mesh(MeshSpec(**work["mesh"]), jax.devices()[:1])
    opt = work["optimizer"]
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh),
        TrainConfig(
            batch_size=work["batch"], optimizer="adamw",
            adam_mu_dtype=opt["mu_dtype"], label_smoothing=0.0,
            fsdp_params=False, train_metrics="loss",
        ),
        mesh, example_input_shape=(2, work["seq_len"]),
        example_input_dtype=jnp.int32, input_key="tokens", label_key="labels",
    )
    return cfg, work["batch"] * work["seq_len"], trainer


CELL_PLANS = {
    # cell: (state bytes, gradient bytes, {name: bytes} in the order of
    # admission, refused, predicted peak) at a v5e's limit.
    "nemotron-3-super-tp2ep64.train-8k": (
        9_190_158_732, 3_676_063_488,
        {
            "moe_route": 146_800_640, "moe_latent_in": 83_886_080,
            "ssm_in_proj": 765_460_480, "mlp_hidden": 440_401_920,
            "attn_qkv": 37_748_736, "ssm_conv": 419_430_400,
            "mixer_gated": 335_544_320,
        },
        (), 15_727_261_836,
    ),
    "laguna-s-2.1-ep32.train-8k": (
        8_110_182_412, 3_244_072_960,
        {
            "attn_gate": 20_971_520, "moe_route": 83_886_080,
            "attn_residual": 251_658_240, "mlp_hidden": 536_870_912,
            "attn_qkv": 822_083_584,
        },
        (), 13_753_397_260,
    ),
    "xing4.0-29b-a4b-ep8.train-8k": (
        7_593_464_472, 3_037_385_784,
        # Four streams: a layer's input and `attn_residual` are 14,336 wide;
        # q's and k's two parts and v; the latents; the maps' raw products
        # and the norm's scalar.
        {
            "hc_maps": 8_192_000, "moe_route": 67_108_864,
            "attn_residual": 1_174_405_120, "mlp_hidden": 436_207_616,
            "attn_latent": 115_343_360, "hc_out": 587_202_560,
            "attn_qkv": 1_184_890_880,
        },
        (), 16_144_994_968,
    ),
    "kimi-linear-48b-a3b-ep32.train-8k": (
        6_024_344_332, 2_409_737_728,
        # Four delta-rule layers: their three projections, the
        # convolutions' results under silu, the gated norm's result, the
        # decay's and the gate's products; the one latent layer's
        # down-projection (no q rank) and its five operands.
        {
            "moe_route": 83_886_080, "attn_residual": 188_743_680,
            "kda_proj": 805_306_368, "mlp_hidden": 436_207_616,
            "attn_latent": 10_485_760, "attn_qkv": 236_978_176,
            "kda_conv": 805_306_368, "mixer_gated": 268_435_456,
            "kda_decay": 268_435_456, "kda_gate": 268_435_456,
        },
        (), 13_596_111_628,
    ),
    "qwen3-next-80b-a3b-ep16.train-8k": (
        6_256_671_372, 2_502_668_544,
        # Three delta-rule layers with a decay a head: q and k at 16 key
        # heads beside v at 32, the gate's product as wide as v, the decay's
        # (with b's beside it) [tokens, 64] float32 in one lane tile; the one
        # attention layer's q, k, v at heads of 256 and its gate a channel,
        # the product beside q's, as wide.
        {
            "attn_gate": 134_217_728, "moe_route": 234_881_024,
            "attn_residual": 268_435_456, "kda_proj": 805_306_368,
            "mlp_hidden": 134_217_728, "attn_qkv": 167_772_160,
            "kda_conv": 805_306_368, "mixer_gated": 402_653_184,
            "kda_decay": 25_165_824, "kda_gate": 402_653_184,
        },
        (), 15_316_368_012,
    ),
    "glm-4.7-flash-ep8.train-8k": (
        7_065_188_492, 2_826_075_392,
        # Five layers AND the multi-token module's block: six residuals, six
        # layers' q and k at a head's two parts side by side (20 x 256
        # lanes each) and v, six latents; five expert layers' routes and
        # shared experts beside the dense layer's 10,240. The peak counts
        # the module's float32 logits and their cotangent beside the main's.
        {
            "moe_route": 83_886_080, "attn_residual": 201_326_592,
            "mlp_hidden": 587_202_560, "attn_latent": 138_412_032,
            "attn_qkv": 1_509_949_440,
        },
        (), 13_987_887_244,
    ),
    "zaya1-8b-ep2.train-8k": (
        9_223_475_372, 3_689_390_144,
        # CCA: q, k and v are no candidate (its backward forms them again).
        {"moe_route": 201_326_592, "attn_residual": 536_870_912},
        (), 15_766_589_612,
    ),
}


@pytest.mark.parametrize("name", sorted(CELL_PLANS))
def test_the_plan_at_a_cells_shapes_under_a_v5es_limit(name):
    """Names in the order of admission, bytes by name, bytes held and the
    predicted peak, pinned; the trainer states the state's and the
    gradients' bytes from `abstract_state()`."""
    state, grads, costs, refused, peak = CELL_PLANS[name]
    cfg, tokens, trainer = _cell(name)
    stated = trainer.step_memory()
    assert stated == StepMemory(state, grads, None)  # the CPU: no limit
    plan = remat_plan(cfg, tokens, dataclasses.replace(stated, limit_bytes=V5E_LIMIT))
    assert plan.names == tuple(n for n in costs if n not in refused)
    assert plan.refused == refused
    assert dict(plan.bytes) == costs
    assert [n for n, _ in plan.bytes] == [n for n in SAVED_RESULTS if n in costs]
    assert plan.saved_bytes == sum(costs[n] for n in plan.names)
    layers = transformer._result_bytes(cfg, tokens)
    at = lambda names: transformer._peak_bytes(cfg, tokens, layers, stated, names)
    assert plan.predicted_peak == at(plan.names) == peak
    assert plan.predicted_peak <= V5E_LIMIT - transformer.REMAT_MARGIN_BYTES
    # A result kept is alive once: the peak grows by no more than its bytes.
    assert state + grads <= at(()) <= peak <= at(()) + plan.saved_bytes


SMALL = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, head_dim=16, d_ff=64,
    remat_policy="flash", attention_impl="dense", dtype=jnp.float32,
)
ROOMY = StepMemory(state_bytes=1 << 20, grad_bytes=1 << 19, limit_bytes=1 << 40)


@pytest.mark.parametrize("stated, policy", [
    (None, "flash"),                                      # nothing stated
    (dataclasses.replace(ROOMY, limit_bytes=None), "flash"),   # the CPU
    (dataclasses.replace(ROOMY, limit_bytes=1 << 21), "flash"),  # no room
    (ROOMY, "full"), (ROOMY, "none"), (ROOMY, "mlp"),     # another policy
])
def test_no_budget_gives_the_kernels_four_names(stated, policy):
    cfg = dataclasses.replace(SMALL, remat_policy=policy)
    plan = remat_plan(cfg, 16, stated)
    assert plan.names == () and plan.saved_bytes == 0
    if policy == "flash" and stated is not None and stated.limit_bytes:
        assert plan.refused == ("attn_residual", "mlp_hidden", "attn_qkv")
        assert plan.predicted_peak > stated.limit_bytes
    else:
        assert plan == RematPlan()


def test_a_name_that_does_not_fit_is_refused_and_the_next_tried():
    cfg = dataclasses.replace(
        SMALL, d_model=512, head_dim=128, num_experts=128, router="sigmoid",
        experts_per_token=2, moe_shared_ff=128,
    )
    costs = dict(remat_plan(cfg, 4096, ROOMY).bytes)
    assert list(costs) == ["moe_route", "attn_residual", "mlp_hidden", "attn_qkv"]
    layers = transformer._result_bytes(cfg, 4096)
    at = lambda *names: transformer._peak_bytes(cfg, 4096, layers, ROOMY, names)
    # Room for the router's and the hidden results, not the d-wide ones.
    room = at("moe_route", "mlp_hidden")
    assert room < at("moe_route", "attn_residual")
    assert room < at("moe_route", "mlp_hidden", "attn_qkv")
    tight = dataclasses.replace(
        ROOMY, limit_bytes=room + transformer.REMAT_MARGIN_BYTES
    )
    plan = remat_plan(cfg, 4096, tight)
    assert plan.names == ("moe_route", "mlp_hidden")
    assert plan.refused == ("attn_residual", "attn_qkv")
    assert plan.predicted_peak == room
    assert plan.saved_bytes == costs["moe_route"] + costs["mlp_hidden"]


def test_bytes_on_a_mesh_are_a_devices_tokens_at_whole_widths():
    """A `dp` (or `sp`) shard holds its share of the tokens and every
    name's bytes follow; a width a `tp` axis would split is counted
    whole, so the predicted peak there is an upper bound (no cell runs
    `flash` on a mesh: PERF.md §7)."""
    cfg = dataclasses.replace(
        SMALL, d_model=256, n_heads=4, head_dim=128, d_ff=1024, num_experts=256,
        router="sigmoid", experts_per_token=2, moe_shared_ff=512,
    )
    one = remat_plan(cfg, 4096, ROOMY)
    shard = remat_plan(cfg, 2048, ROOMY)
    ratio = {n: b / dict(one.bytes)[n] for n, b in shard.bytes}
    assert ratio == {
        "moe_route": 0.5, "attn_residual": 0.5, "attn_qkv": 0.5,
        "mlp_hidden": 0.5,
    }
    # Two layers of q, k and v, float32, four heads of 128 each, whole.
    assert dict(one.bytes)["attn_qkv"] == 2 * 4096 * 4 * 3 * (4 * 128)


def test_under_cca_q_k_v_are_no_candidate():
    """CCA's mixing reads the projections' results in its backward, so
    they are formed again whatever is kept behind it (zaya: ~0 ms for
    0.40 GB, PERF.md §6 PR 38): not named, not counted, not admitted,
    though the floor still counts them among what a layer forms again."""
    plain = dataclasses.replace(SMALL, n_kv_heads=2, rope_fraction=0.5)
    cca = dataclasses.replace(plain, cca=True)
    assert "attn_qkv" in remat_plan(plain, 16, ROOMY).names
    plan = remat_plan(cca, 16, ROOMY)
    assert plan.names == ("attn_residual", "mlp_hidden") and plan.refused == ()
    assert "attn_qkv" not in dict(plan.bytes)
    none_kept = lambda cfg: transformer._peak_bytes(
        cfg, 16, transformer._result_bytes(cfg, 16), ROOMY
    )
    assert none_kept(cca) == none_kept(plain)
    named = {
        e.params["name"] for e in _walk_eqns(_forward_jaxpr(cca))
        if e.primitive.name == "name"
    }
    assert "attn_qkv" not in named and "attn_residual" in named


def _text(jaxpr) -> str:
    """A jaxpr's text without the addresses its function objects print."""
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


def _trainer(cfg, mesh_spec=MeshSpec(), devices=1, batch=2, seq=8, accum_steps=1):
    mesh = build_mesh(mesh_spec, jax.devices()[:devices])
    return Trainer(
        TransformerLM(cfg, mesh=mesh),
        TrainConfig(batch_size=batch, optimizer="adamw", label_smoothing=0.0,
                    fsdp_params=False, train_metrics="loss",
                    accum_steps=accum_steps),
        mesh, example_input_shape=(batch, seq), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )


def _forward_jaxpr(cfg, shape=(2, 8)):
    model = TransformerLM(cfg)
    tokens = jnp.zeros(shape, jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    return jax.make_jaxpr(model.apply)(params, tokens).jaxpr


def _calls(monkeypatch):
    """Every `remat_plan` call a trace makes: (tokens, stated, plan)."""
    seen, real = [], transformer.remat_plan

    def spy(cfg, tokens, stated):
        plan = real(cfg, tokens, stated)
        seen.append((tokens, stated, plan))
        return plan

    monkeypatch.setattr(transformer, "remat_plan", spy)
    return seen


def test_every_trace_of_a_trainers_step_reads_one_plan(monkeypatch):
    """`abstract_state()`'s shapes give the bytes the step states, so the
    step `fit()` compiles and a later lowering of it (`step_scopes()`)
    are one program; the model's own init and an `apply` outside the
    trainer find nothing stated."""
    monkeypatch.setattr(memory, "device_limit", lambda mesh: 1 << 40)
    seen = _calls(monkeypatch)
    trainer = _trainer(SMALL)
    stated = trainer.step_memory()
    assert stated.limit_bytes == 1 << 40 and stated.state_bytes > stated.grad_bytes > 0
    abstract = trainer.abstract_state()
    assert [s for _, s, _ in seen] == [None]  # the init's trace
    tokens = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    step = trainer.make_train_step()
    first = _text(step.trace(abstract, batch).jaxpr)
    jax.clear_caches()
    again = _text(trainer.make_train_step().trace(abstract, batch).jaxpr)
    assert first == again
    in_step = seen[1:]
    assert in_step and all(call == in_step[0] for call in in_step)
    assert in_step[0] == (16, stated, remat_plan(SMALL, 16, stated))
    assert in_step[0][2].names == ("attn_residual", "mlp_hidden", "attn_qkv")
    del seen[:]
    trainer.model.apply(
        {"params": jax.tree_util.tree_map(jnp.zeros_like, abstract.params)},
        jnp.zeros((2, 8), jnp.int32),
    )
    assert [s for _, s, _ in seen] == [None]


class _Device:
    """A device as a process of a larger job sees it: its own report a
    limit, another process's raise as this jaxlib's do."""

    def __init__(self, process_index: int, limit: int | None = None):
        self.process_index, self.limit = process_index, limit
        self.client = self

    def memory_stats(self):
        if self.limit is None:
            raise jax.errors.JaxRuntimeError(
                "INVALID_ARGUMENT: MemoryStats is only supported for "
                "addressable PjRt devices."
            )
        return {"bytes_limit": self.limit, "bytes_in_use": 12345}


def _mesh_as_seen_by(process: int):
    """A mesh of two chips, one a process: a device reports its limit to
    the process that owns it alone."""
    devices = np.array([
        _Device(owner, V5E_LIMIT if owner == process else None)
        for owner in (0, 1)
    ])
    return type("MeshStub", (), {
        "devices": devices, "local_devices": [devices[process]],
    })()


def test_the_limit_is_read_off_a_device_this_process_addresses():
    """Process 1 of two: the mesh's first device is process 0's and its
    stats raise. Each process reads the limit off its own chip, one kind,
    so both state the same and hold one plan. A described device (local,
    but its stats raise) and the CPU (no limit in its stats) give None."""
    second = _mesh_as_seen_by(1)
    with pytest.raises(jax.errors.JaxRuntimeError):
        second.devices.flat[0].memory_stats()
    stated = [
        StepMemory(1 << 20, 1 << 19, memory.device_limit(_mesh_as_seen_by(process)))
        for process in (0, 1)
    ]
    assert stated[0] == stated[1] and stated[1].limit_bytes == V5E_LIMIT
    assert remat_plan(SMALL, 16, stated[0]) == remat_plan(SMALL, 16, stated[1])
    assert remat_plan(SMALL, 16, stated[1]).names
    described = type("MeshStub", (), {"local_devices": [_Device(0)]})()
    assert memory.device_limit(described) is None
    assert memory.device_limit(_trainer(SMALL).mesh) is None  # the CPU


def test_a_trainer_whose_meshs_first_device_is_anothers_states_its_own(monkeypatch):
    """`Trainer.step_memory()` asks the mesh for a device of this
    process: with the first device's stats unreadable (here the CPU's,
    which report no limit) the step still states the local chip's limit,
    and the plan is the one taken from it."""
    trainer = _trainer(SMALL, MeshSpec(dp=2), devices=2)
    assert trainer.step_memory().limit_bytes is None
    monkeypatch.setattr(
        type(trainer.mesh), "local_devices",
        property(lambda mesh: [_Device(jax.process_index(), V5E_LIMIT)]),
    )
    stated = trainer.step_memory()
    assert stated.limit_bytes == V5E_LIMIT
    seen = _calls(monkeypatch)
    tokens = jax.ShapeDtypeStruct(
        (2, 8), jnp.int32, sharding=trainer.batch_sharding(2)
    )
    trainer.make_train_step().trace(
        trainer.abstract_state(), {"tokens": tokens, "labels": tokens}
    )
    assert seen[-1] == (8, stated, remat_plan(SMALL, 8, stated))
    assert seen[-1][2].names == ("attn_residual", "mlp_hidden", "attn_qkv")


def test_under_accumulation_nothing_is_stated(monkeypatch):
    """`accum_steps=2`: the scan's backward holds more beside the state
    than the floor counts (the accumulated gradients, a tick's and the
    scan's own: PERF.md §7), so the step states nothing and the layers
    keep the kernels' results alone, whatever room the device has."""
    monkeypatch.setattr(memory, "device_limit", lambda mesh: 1 << 40)
    seen = _calls(monkeypatch)
    once, twice = _trainer(SMALL, batch=4), _trainer(SMALL, batch=4, accum_steps=2)
    assert twice.step_memory() == once.step_memory()
    tokens = jax.ShapeDtypeStruct((4, 8), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    once.make_train_step().trace(once.abstract_state(), batch)
    assert seen[-1][:2] == (32, once.step_memory()) and seen[-1][2].names
    held = _text(twice.make_train_step().trace(twice.abstract_state(), batch).jaxpr)
    assert seen[-1] == (16, None, RematPlan())
    monkeypatch.setattr(memory, "device_limit", lambda mesh: None)  # the CPU
    assert held == _text(
        twice.make_train_step().trace(twice.abstract_state(), batch).jaxpr
    )


def test_the_model_counts_a_devices_tokens_and_shard_on_a_mesh(monkeypatch):
    monkeypatch.setattr(memory, "device_limit", lambda mesh: 1 << 40)
    seen = _calls(monkeypatch)
    trainer = _trainer(SMALL, MeshSpec(dp=2, tp=2), devices=4, batch=4)
    whole, quarter = _trainer(SMALL).step_memory(), trainer.step_memory()
    # Matrices are split over `tp`, the norms' scales on every device.
    assert whole.state_bytes / 2 < quarter.state_bytes < whole.state_bytes * 0.6
    tokens = jax.ShapeDtypeStruct(
        (4, 8), jnp.int32, sharding=trainer.batch_sharding(2)
    )
    trainer.make_train_step().trace(
        trainer.abstract_state(), {"tokens": tokens, "labels": tokens}
    )
    assert seen[-1][:2] == (16, quarter)  # half the batch's 32 tokens


def _dots(jaxpr, inside: bool):
    """`dot_general`s of a gradient's jaxpr inside (or outside) its
    checkpoints' equations."""
    count = 0
    for eqn in jaxpr.eqns:
        again = eqn.primitive.name in ("checkpoint", "remat2")
        if eqn.primitive.name == "dot_general" and not inside:
            count += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if again:
                if inside:
                    count += sum(
                        e.primitive.name == "dot_general" for e in _walk_eqns(sub)
                    )
            else:
                count += _dots(sub, inside)
    return count


def _grad_jaxpr(cfg, stated, shape=(2, 8)):
    model = TransformerLM(cfg)
    tokens = jnp.arange(
        shape[0] * shape[1], dtype=jnp.int32
    ).reshape(shape) % cfg.vocab_size
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        with memory.stated(stated):
            return model.apply(p, tokens).astype(jnp.float32).sum()

    return jax.make_jaxpr(jax.grad(loss))(params).jaxpr


@pytest.mark.parametrize("limit, refused", [
    (16_500_000_000, ("attn_qkv",)),
    (15_000_000_000, ("hc_out", "attn_qkv")),
    (14_000_000_000, ("attn_residual", "hc_out", "attn_qkv")),
    (12_000_000_000, ("hc_maps", "moe_route", "attn_residual", "mlp_hidden",
                      "attn_latent", "hc_out", "attn_qkv")),
])
def test_the_streams_cell_is_near_the_limit_and_a_smaller_one_refuses(
    limit, refused
):
    """The xing cell's predicted peak leaves 0.27 GB under a v5e's limit
    less the margin with every name kept (16.11 of 16.91 - 0.54 GB): a
    device with 0.4 GB less keeps q, k and v out, and what follows a
    refusal is still tried (the latents after the residual streams)."""
    cfg, tokens, trainer = _cell("xing4.0-29b-a4b-ep8.train-8k")
    stated = dataclasses.replace(trainer.step_memory(), limit_bytes=limit)
    plan = remat_plan(cfg, tokens, stated)
    assert plan.refused == refused
    assert set(plan.names) | set(refused) == {n for n, _ in plan.bytes}
    assert plan.predicted_peak <= limit - transformer.REMAT_MARGIN_BYTES or (
        not plan.names
    )
    costs = dict(plan.bytes)
    # four streams wide: a layer's kept stream is four times a d_model's
    assert costs["attn_residual"] == 5 * tokens * 4 * cfg.d_model * 2
    assert costs["attn_latent"] == 5 * tokens * (768 + 640) * 2
    assert costs["hc_out"] == 5 * 2 * tokens * cfg.d_model * 2
    assert costs["attn_qkv"] == 5 * tokens * 2 * (32 * (128 + 64 + 128 + 128) + 128)
    assert costs["hc_maps"] == 5 * 2 * tokens * (24 + 1) * 4


def test_a_result_kept_is_not_formed_again():
    """One layer of attention and SwiGLU: seven matmuls forward (q, k, v,
    o, gate, up, down) and the scores' two. With nothing stated the
    checkpoint forms all but the last again (the dense attention's two
    with them); with every name kept only the scores' two, which no name
    covers; with room for the residual alone, `wo` is spared and the
    rest is formed again."""
    cfg = dataclasses.replace(SMALL, n_layers=1)
    plain = _grad_jaxpr(cfg, None)
    assert _text(plain) == _text(_grad_jaxpr(
        cfg, dataclasses.replace(ROOMY, limit_bytes=1 << 21)
    ))
    every = _grad_jaxpr(cfg, ROOMY)
    # Few gradients, so the top layer's backward is the fuller moment and
    # every result kept counts (the bottom layer's holds its own once).
    lean = dataclasses.replace(ROOMY, grad_bytes=0)
    with_residual = transformer._peak_bytes(
        cfg, 16, transformer._result_bytes(cfg, 16), lean, ("attn_residual",)
    )
    residual_only = _grad_jaxpr(cfg, dataclasses.replace(
        lean, limit_bytes=with_residual + transformer.REMAT_MARGIN_BYTES,
    ))
    # The checkpoint's equation in a gradient holds what is formed again
    # and the backward's own two matmuls for each of the nine.
    assert _dots(plain, inside=True) == 8 + 18
    assert _dots(residual_only, inside=True) == 7 + 18
    assert _dots(every, inside=True) == 2 + 18
    outside = {_dots(j, inside=False) for j in (plain, residual_only, every)}
    assert len(outside) == 1  # the forward and the backward's own: unmoved


FAMILIES = {
    "blocks, gate, sigmoid experts, a leading dense layer": dict(
        n_layers=2, num_experts=4, router="sigmoid", experts_per_token=2,
        moe_shared_ff=32, attention_gate=True, dense_layers=1, dense_d_ff=96,
        n_kv_heads=1,
    ),
    "blocks, CCA, the router MLP": dict(
        n_layers=2, num_experts=4, router="mlp", router_hidden=16, cca=True,
        n_kv_heads=2, rope_fraction=0.5,
    ),
    "blocks of four streams, latent attention, sigmoid experts": dict(
        n_layers=2, num_experts=4, router="sigmoid", experts_per_token=2,
        moe_shared_ff=32, dense_layers=1, dense_d_ff=96, q_latent=12,
        kv_latent=8, rope_head_dim=8, residual_streams=4, tie_embeddings=False,
    ),
    "blocks of four streams as kernels, latent attention, sigmoid experts": dict(
        n_layers=2, num_experts=4, router="sigmoid", experts_per_token=2,
        moe_shared_ff=32, dense_layers=1, dense_d_ff=96, q_latent=12,
        kv_latent=8, rope_head_dim=8, residual_streams=4, tie_embeddings=False,
        d_model=128,
    ),
    "blocks of delta mixers beside un-rotated latent attention": dict(
        n_layers=2, num_experts=4, router="sigmoid", experts_per_token=2,
        moe_shared_ff=32, dense_layers=1, dense_d_ff=96, kv_latent=8,
        rope_head_dim=8, tie_embeddings=False, ssm_chunk=8,
        attention_kinds=(
            transformer.AttentionKind(2, mixer="delta"),
            transformer.AttentionKind(2, rope_fraction=0.0),
        ),
        attention_pattern=(0, 1),
    ),
    "blocks of delta mixers as kernels beside un-rotated latent attention": dict(
        n_layers=2, num_experts=4, router="sigmoid", experts_per_token=2,
        moe_shared_ff=32, dense_layers=1, dense_d_ff=96, kv_latent=8,
        rope_head_dim=8, tie_embeddings=False, ssm_chunk=8, head_dim=128,
        attention_kinds=(
            transformer.AttentionKind(2, mixer="delta"),
            transformer.AttentionKind(2, rope_fraction=0.0),
        ),
        attention_pattern=(0, 1),
    ),
    "a pattern of mixers as kernels, relu2 experts and attention": dict(
        n_layers=3, layer_pattern="ME*", num_experts=4, router="sigmoid",
        experts_per_token=2, moe_latent=16, moe_shared_ff=48, mlp_act="relu2",
        ssm_heads=8, ssm_head_dim=32, ssm_state=64, ssm_groups=2, ssm_chunk=8,
        rope_fraction=0.0, tie_embeddings=False, n_kv_heads=1,
    ),
    "a pattern of mixers, latent relu2 experts and attention": dict(
        n_layers=3, layer_pattern="ME*", num_experts=4, router="sigmoid",
        experts_per_token=2, moe_latent=16, moe_shared_ff=48, mlp_act="relu2",
        ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=8,
        rope_fraction=0.0, tie_embeddings=False, n_kv_heads=1,
    ),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_plans_bytes_are_those_of_the_results_the_layers_name(
    family, monkeypatch
):
    """What `_result_bytes` reckons from the configuration is what the
    traced forward names: every `name` equation's result, its minor
    dimension in whole lane tiles, summed by name. The streams' mixes as
    XLA's code and as the row-block kernels, the mixers' convolutions as
    XLA's passes and as the `shortconv_*` pair, their gated norm as the
    `gatenorm_*` pair (the CPU is told they compile) name the same
    results: under the pair `kda_conv` sits on q, k and v as the delta
    rule reads them, the pre-activations' bytes."""
    cfg = dataclasses.replace(SMALL, dtype=jnp.bfloat16, **FAMILIES[family])
    kernels = "as kernels" in family
    if kernels:
        for ops in (streams_ops, shortconv, gatenorm):
            monkeypatch.setattr(ops, "kernels_apply", functools.partial(
                ops.kernels_apply, compiled=True
            ))
    # the kernels take sequences of whole 128-row blocks
    shape = (2, 128) if kernels else (2, 8)
    forward = _forward_jaxpr(cfg, shape)
    assert kernels == bool(
        {"hc_pre_fwd", "shortconv_fwd"} & set(jaxpr_kernel_names(forward))
    )
    mixers = "M" in (cfg.layer_pattern or "") or any(
        kind.mixer == "delta" for kind in cfg.attention_kinds
    )
    assert (kernels and mixers) == ("gatenorm_fwd" in jaxpr_kernel_names(forward))
    named: dict = {}
    for eqn in _walk_eqns(forward):
        if eqn.primitive.name == "name" and eqn.params["name"] in SAVED_RESULTS:
            aval = eqn.outvars[0].aval
            size = int(np.prod(aval.shape[:-1])) * transformer._lanes(aval.shape[-1])
            if eqn.params["name"] == transformer.HC_RESULT:
                # [B, maps, S]: the sequence in the lanes, whole tiles at
                # any real length, so counted plain.
                size = int(np.prod(aval.shape))
            named[eqn.params["name"]] = (
                named.get(eqn.params["name"], 0) + size * aval.dtype.itemsize
            )
    assert named == dict(remat_plan(cfg, shape[0] * shape[1], ROOMY).bytes)
    assert set(KERNEL_RESULTS).isdisjoint(SAVED_RESULTS)


def test_the_multi_token_module_is_one_more_block_and_one_more_head_of_the_plan():
    """With `mtp_layers` the plan's bytes are those of the results the
    layers AND the module's block name (traced with labels: without them
    the module does not run), its latent attention's q and k at a head's
    two parts side by side; every checkpoint's bytes gain the block's and
    what its projection reads; the top of the backward gains the module's
    float32 logits and their cotangent."""
    joined = dict(
        n_layers=2, num_experts=4, router="sigmoid", experts_per_token=2,
        moe_shared_ff=32, dense_layers=1, dense_d_ff=96, q_latent=12,
        kv_latent=8, head_dim=24, rope_head_dim=8, v_head_dim=32,
        tie_embeddings=False, dtype=jnp.bfloat16,
    )
    plain = dataclasses.replace(SMALL, **joined)
    cfg = dataclasses.replace(plain, mtp_layers=1)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    forward = jax.make_jaxpr(
        lambda p, t: model.apply(p, t, labels=t, mutable=["counters"])
    )(params, tokens).jaxpr
    named: dict = {}
    for eqn in _walk_eqns(forward):
        if eqn.primitive.name == "name" and eqn.params["name"] in SAVED_RESULTS:
            aval = eqn.outvars[0].aval
            size = int(np.prod(aval.shape[:-1])) * transformer._lanes(aval.shape[-1])
            named[eqn.params["name"]] = (
                named.get(eqn.params["name"], 0) + size * aval.dtype.itemsize
            )
    assert named == dict(remat_plan(cfg, 16, ROOMY).bytes)
    with_module, without = (
        transformer._result_bytes(c, 16) for c in (cfg, plain)
    )
    # the module's block: an expert layer with the last layer's attention
    assert len(with_module) == 3 and with_module[:2] == without
    assert with_module[2] == without[1]
    lanes = transformer._lanes
    # q and k joined (2 heads x (8 + 24)) and v (2 x 32): the turned key
    # is inside k
    assert without[0][transformer.QKV_RESULT] == 16 * 2 * 3 * lanes(64)
    kept = transformer._kept_always_bytes
    block = 16 * (lanes(32) * 2 + lanes(2 * 32) * 2 + lanes(2) * 4)
    assert kept(cfg, 16) - kept(plain, 16) == block + 16 * lanes(2 * 32) * 2
    logits = 16 * lanes(64) * 4
    top = StepMemory(state_bytes=1 << 20, grad_bytes=0, limit_bytes=1 << 40)
    at = lambda c, layers: transformer._peak_bytes(c, 16, layers, top)
    assert at(cfg, with_module) - at(plain, without) == (
        2 * logits + kept(cfg, 16) - kept(plain, 16)
    )


def test_a_delta_rule_layer_keeps_its_kernels_results_and_counts_its_work():
    """`kda_out` and `kda_states` are kept whatever the plan (o, and a
    [d, H·d] state a chunk); the layer's second forward holds
    `KDA_WORK_ARRAYS` float32 arrays beside the named results, under no
    name, so no plan admits them."""
    from kubeflow_tpu.ops import kda

    assert {kda.CHECKPOINT_OUT_NAME, kda.CHECKPOINT_STATES_NAME} <= set(KERNEL_RESULTS)
    cfg, tokens, _ = _cell("kimi-linear-48b-a3b-ep32.train-8k")
    layers = transformer._result_bytes(cfg, tokens)
    wide = tokens * 4096
    assert [("kda_proj" in layer, "attn_qkv" in layer) for layer in layers] == [
        (True, False), (True, False), (True, False), (False, True), (True, False)
    ]
    assert layers[0]["kda_work"] == 2 * wide * 4 == 268_435_456
    assert layers[0]["kda_conv"] == 3 * wide * 2 == 201_326_592
    for name in ("mixer_gated", "kda_gate"):
        assert layers[0][name] == wide * 2 and name not in layers[3]
    assert "kda_work" not in SAVED_RESULTS and "mlp_hidden" in layers[0]
    sched = kda.kda_schedule(8192, heads=32, head_dim=128, chunk=cfg.ssm_chunk)
    stream = tokens * 2304 * 2
    latent = tokens * (4096 * 2 + 128 * 4)  # o and a log-sum-exp a head
    assert transformer._kept_always_bytes(cfg, tokens) == (
        5 * stream + 4 * sched["saved_bytes_a_call"] + latent
    )


def test_a_decay_a_head_over_grouped_key_heads_counts_its_own_widths():
    """The qwen3-next cell's rows: a delta layer's q and k are its KEY
    heads wide, its decay one float32 a value head (a lane tile a token, not
    a head's lanes), its heads' width the row's own beside attention's 256;
    the channel gate's product is as wide as q; o and the chunk states are
    a VALUE head's, kept whatever the plan."""
    from kubeflow_tpu.ops import kda

    cfg, tokens, _ = _cell("qwen3-next-80b-a3b-ep16.train-8k")
    assert tokens == 16_384 and cfg.head_dim == 256
    delta, attention = cfg.attention_kinds
    assert (delta.n_heads, delta.key_heads, delta.head_dim) == (32, 16, 128)
    assert (delta.decay, delta.gate_act) == ("head", "silu")
    layers = transformer._result_bytes(cfg, tokens)
    assert [("kda_proj" in layer, "attn_qkv" in layer) for layer in layers] == [
        (True, False), (True, False), (True, False), (False, True)
    ]
    keys, wide = tokens * 2048, tokens * 4096
    assert layers[0]["kda_proj"] == layers[0]["kda_conv"] == (2 * keys + wide) * 2
    assert layers[0]["kda_decay"] == tokens * 128 * 4   # [tokens, 64] in a tile
    assert layers[0]["kda_work"] == 2 * layers[0]["kda_decay"]
    for name in ("mixer_gated", "kda_gate"):
        assert layers[0][name] == wide * 2 and name not in layers[3]
    assert layers[3]["attn_gate"] == tokens * 16 * 256 * 2
    assert layers[3]["attn_qkv"] == tokens * (16 + 2 + 2) * 256 * 2
    # softmax over 512 and three [tokens, 10] arrays, a lane tile each
    assert layers[0]["moe_route"] == tokens * 4 * (512 + 3 * 128)
    sched = kda.kda_schedule(
        8192, heads=32, key_heads=16, head_dim=128, chunk=cfg.ssm_chunk, batch=2
    )
    stream = tokens * 2048 * 2
    full = tokens * (4096 * 2 + 128 * 4)  # o and a log-sum-exp a head
    assert transformer._kept_always_bytes(cfg, tokens) == (
        4 * stream + 3 * sched["saved_bytes_a_call"] + full
    )


@pytest.mark.parametrize("family, calls", [
    ("blocks of delta mixers as kernels beside un-rotated latent attention", 3),
    ("a pattern of mixers as kernels, relu2 experts and attention", 1),
])
def test_a_convolution_kept_runs_no_forward_kernel_again(
    family, calls, monkeypatch
):
    """`kda_proj` + `kda_conv` (`ssm_in_proj` + `ssm_conv`) kept: the
    gradient holds the forward's `shortconv_fwd` calls (q, k and v; xBC)
    and no second set, and one `shortconv_bwd` each, whose only reads are
    the kept projection and the cotangent. `mixer_gated` kept: the one
    `gatenorm_fwd` of the mixer layer and no second (its operands are the
    kernels' results and the kept projections: the backward reads them,
    the out-projection's weight gradient the kept result). With nothing
    stated the checkpoint forms both again."""
    for ops in (shortconv, gatenorm):
        monkeypatch.setattr(ops, "kernels_apply", functools.partial(
            ops.kernels_apply, compiled=True
        ))
    cfg = dataclasses.replace(SMALL, dtype=jnp.bfloat16, **FAMILIES[family])
    plan = remat_plan(cfg, 256, ROOMY)
    assert plan.refused == () and "mixer_gated" in plan.names and (
        {"kda_proj", "kda_conv"} <= set(plan.names)
        or {"ssm_in_proj", "ssm_conv"} <= set(plan.names)
    )
    for stated, again in ((ROOMY, 1), (None, 2)):
        names = jaxpr_kernel_names(_grad_jaxpr(cfg, stated, shape=(2, 128)))
        assert names.count("shortconv_fwd") == again * calls, (stated, names)
        assert names.count("shortconv_bwd") == calls
        assert names.count("gatenorm_fwd") == again, (stated, names)
        assert names.count("gatenorm_bwd") == 1


@pytest.mark.parametrize("name", sorted(CELL_PLANS))
def test_a_name_only_a_mixer_forms_costs_the_other_cells_nothing(
    name, monkeypatch
):
    """`mixer_gated` is formed by a recurrent mixer alone: without it in
    `SAVED_RESULTS` the cells with no such mixer get the plan they get
    with it, byte for byte, and the three with one lose that name alone
    (`kda_gate`, the delta mixer's own, likewise costs kimi and qwen3-next
    alone)."""
    cfg, tokens, trainer = _cell(name)
    stated = dataclasses.replace(trainer.step_memory(), limit_bytes=V5E_LIMIT)
    plan = remat_plan(cfg, tokens, stated)
    monkeypatch.setattr(transformer, "SAVED_RESULTS", tuple(
        n for n in SAVED_RESULTS if n != transformer.GATED_RESULT
    ))
    without = remat_plan(cfg, tokens, stated)
    if name.startswith(("kimi", "nemotron", "qwen3-next")):
        assert set(plan.names) - set(without.names) == {"mixer_gated"}
        layers_by_tokens = {"nemotron": 5, "kimi": 4, "qwen3-next": 2 * 3}
        assert plan.saved_bytes - without.saved_bytes == dict(plan.bytes)[
            "mixer_gated"
        ] == 8192 * 4096 * 2 * next(
            n for who, n in layers_by_tokens.items() if name.startswith(who)
        )
    else:
        assert plan == without


def test_the_kimi_cells_predicted_peak_bounds_the_compilers_count():
    """With the gated norm's float32 arrays in VMEM a delta layer's work
    is the log decay and its gradient (`KDA_WORK_ARRAYS` 2): the plan's
    peak still lies over what the chip's compiler counts for the cell's
    whole step (arguments, temporaries and code, compiled for a described
    v5e: 13,161,131,520 bytes, PERF.md §6, PR 43), with all ten names
    admitted."""
    cfg, tokens, trainer = _cell("kimi-linear-48b-a3b-ep32.train-8k")
    plan = remat_plan(cfg, tokens, dataclasses.replace(
        trainer.step_memory(), limit_bytes=V5E_LIMIT
    ))
    assert transformer.KDA_WORK_ARRAYS == 2 and plan.refused == ()
    assert 13_161_131_520 < plan.predicted_peak < 13_161_131_520 + (1 << 29)


def test_fit_records_how_far_the_plan_engaged(monkeypatch):
    cfg = dataclasses.replace(SMALL, n_layers=1)
    tokens = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    data = [{"tokens": tokens, "labels": tokens}] * 2
    records: list = []
    fit(_trainer(cfg), data, 1, log_every=1, handle_signals=False,
        on_metrics=lambda step, rec: records.append(rec))
    assert "remat_saved_bytes" not in records[0]  # the CPU: no limit known
    monkeypatch.setattr(memory, "device_limit", lambda mesh: 1 << 40)
    trainer = _trainer(cfg)
    fit(trainer, data, 1, log_every=1, handle_signals=False,
        on_metrics=lambda step, rec: records.append(rec))
    plan = remat_plan(cfg, 16, trainer.step_memory())
    assert records[1]["remat_saved_bytes"] == plan.saved_bytes > 0
    assert records[1]["remat_names"] == len(plan.names) == 3
    assert records[1]["loss"] == pytest.approx(records[0]["loss"], rel=1e-6)
