"""Compiled-program accounting: collective and schedule introspection.

On a CPU mesh a silently re-replicated sharding still converges, so
finite loss/grads alone can't prove a program runs the intended
communication pattern. These helpers inspect the COMPILED
(post-SPMD-partitioner) HLO text and the traced jaxpr instead — shared
by the driver's `dryrun_multichip`, `bench.py --workload pipeline`, and
the collective-accounting regression tests, so all three count the same
things the same way.
"""

from __future__ import annotations

import re
from typing import Iterable

# The collective families the platform's programs are audited against.
# dynamic-slice rides along because the CPU backend emits the unfused
# all-reduce + dynamic-slice form of reduce-scatter.
COLLECTIVE_OPS: tuple[str, ...] = (
    "all-gather",
    "reduce-scatter",
    "all-reduce",
    "collective-permute",
    "all-to-all",
    "dynamic-slice",
)


def compiled_hlo(jitted, *args) -> str:
    """Post-partitioner HLO text for a jitted callable at `args`."""
    return jitted.lower(*args).compile().as_text()


def collective_counts(hlo: str) -> dict[str, int]:
    """Occurrences of each collective family in HLO text."""
    return {op: len(re.findall(rf"\b{op}", hlo)) for op in COLLECTIVE_OPS}


_START_OPS = "|".join(op for op in COLLECTIVE_OPS if op != "dynamic-slice")


def async_collective_counts(hlo: str) -> dict[str, int]:
    """Collectives of compiled HLO text that the scheduler may run beside
    compute, by the form they take:

    - `start`: instructions whose opcode is `<collective>-start` (its
      `-done` closes the interval);
    - `fusion`: instructions of the entry computation that call an
      `async_collective_fusion` computation (the TPU compiler's form: the
      collective in one fusion with the compute it runs beside);
    - `tagged`: collectives carrying an `async_collective_name` attribute
      (the TPU compiler's final text keeps the plain opcode and names the
      start it scheduled there).

    All zero is a program whose collectives are synchronous."""
    entry = re.search(r"^ENTRY .*?^\}", hlo, re.M | re.S)
    return {
        "start": len(re.findall(rf"\s(?:{_START_OPS})-start\(", hlo)),
        "fusion": len(re.findall(
            r"calls=%?async_collective_fusion", entry.group(0) if entry else ""
        )),
        "tagged": len(re.findall(r'async_collective_name="', hlo)),
    }


def assert_collectives(
    name: str,
    hlo: str,
    expect: Iterable[str] = (),
    forbid: Iterable[str] = (),
    quiet: bool = False,
) -> dict[str, int]:
    """Assert expected collectives are present — and the wrong ones
    absent — in compiled HLO; returns the counts. Prints the one-line
    summary the driver's dryrun artifact parses."""
    counts = collective_counts(hlo)
    for op in expect:
        assert counts[op] > 0, (
            f"{name}: expected {op!r} in compiled HLO but found none "
            f"(counts: {counts}) — the sharding silently degenerated"
        )
    for op in forbid:
        assert counts[op] == 0, (
            f"{name}: forbidden {op!r} appears {counts[op]}x in "
            f"compiled HLO (counts: {counts}) — the program is "
            f"materializing what it should stream"
        )
    if not quiet:
        print(
            f"{name} collectives: "
            + " ".join(f"{op}={counts[op]}" for op in COLLECTIVE_OPS)
        )
    return counts


_SHAPE = re.compile(r"\w+\[([0-9,]*)\]")


def allreduce_element_counts(hlo: str) -> list[int]:
    """Element count of every all-reduced buffer in HLO text (each
    component of a tuple-shaped all-reduce counts separately). This is
    how the pipeline layer's wire contract is audited: a training step
    whose cross-pp traffic is scalars plus replicated-weight gradients
    shows nothing here near activation size, while an all-reduce of a
    `[M, mb, ...]` activation buffer sticks out by orders of
    magnitude."""
    out = []
    for m in re.finditer(r"=\s*([^=\n]*?)\s+all-reduce(?:-start)?\(", hlo):
        for dims in _SHAPE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            out.append(n)
    return out


def replica_group_shapes(hlo: str) -> set[str]:
    """'contiguous' and/or 'strided' group patterns present in the
    HLO's replica_groups — contiguous groups are within-slice (ICI)
    partitions, strided groups cross slices (DCN). Handles both the
    explicit v1 form ({{0,1},{2,3}}) and the iota v2 form
    ([G,S]<=[8] / [G,S]<=[2,4]T(1,0) — a transpose means the minor
    axis strides across the device order)."""
    shapes = set()
    for m in re.finditer(r"replica_groups=\{(\{[^=]*?\})\}", hlo):
        for grp in re.findall(r"\{([\d,]+)\}", m.group(1)):
            ids = [int(x) for x in grp.split(",")]
            if len(ids) < 2:
                continue
            strides = {b - a for a, b in zip(ids, ids[1:])}
            shapes.add("contiguous" if strides == {1} else "strided")
    for m in re.finditer(
        r"replica_groups=\[(\d+),(\d+)\]<=\[[\d,]+\](T\([\d,]+\))?",
        hlo,
    ):
        n_groups, group_size, transpose = (
            int(m.group(1)), int(m.group(2)), m.group(3),
        )
        if n_groups <= 1 or group_size <= 1:
            continue  # one global group / singleton groups: neither
        shapes.add("strided" if transpose else "contiguous")
    return shapes


def _walk_eqns(jaxpr):
    """Every equation of `jaxpr`, sub-jaxprs (jit, custom_vjp, remat,
    shard_map, scan, cond bodies) included."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


def jaxpr_kernel_names(jaxpr) -> list[str]:
    """The `name=` of every `pallas_call` in `jaxpr`, in program order,
    one entry per call — which kernels a program runs, read from the
    equations themselves. (Counting kernel function names in
    `str(jaxpr)` reads 0 under the installed jax: an equation prints its
    `name`, which is None unless the call site passes one.) A call
    without a name is reported as "" so it still counts."""
    return [
        eqn.params.get("name") or ""
        for eqn in _walk_eqns(jaxpr)
        if eqn.primitive.name == "pallas_call"
    ]


def pallas_kernel_names(fn, *args) -> list[str]:
    """`jaxpr_kernel_names` of the program `fn(*args)` traces."""
    import jax

    return jaxpr_kernel_names(jax.make_jaxpr(fn)(*args).jaxpr)


def tpu_kernel_calls(hlo: str) -> int:
    """Compiled Pallas kernels in HLO text: one `tpu_custom_call`
    custom-call per launch site (interpreted kernels leave none)."""
    return hlo.count('custom_call_target="tpu_custom_call"')


def scan_lengths(fn, *args) -> set[int]:
    """Trip counts of every `lax.scan`/`fori_loop` in `fn`'s jaxpr
    (recursively, so scans inside shard_map/checkpoint/vmap bodies are
    seen). The pipeline bench uses this to read the schedule's MEASURED
    tick count out of the traced program rather than trusting the model
    formula it is compared against."""
    import jax

    # fori_loop with static bounds carries them as consts in the cond
    # jaxpr only when not lowered to scan; nothing to read generically
    # from a `while` — scan is the differentiable form the pipeline uses.
    return {
        int(eqn.params["length"])
        for eqn in _walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        if eqn.primitive.name == "scan"
    }
