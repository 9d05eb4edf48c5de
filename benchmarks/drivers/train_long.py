"""Driver of the `train_long` kind: `drivers/train.py`'s run, as it stands,
with a reference that fits beside a long sequence.

At S = 8192 the dense decoder's plain reference (`reference/lm.py`) keeps
its [heads, S, S] scores in float32, 4.3 GB a tensor at 16 heads: with its
state at 16 bytes a parameter resident, `lm.follow`'s one step program asks
for 18.3 GB of a v5e's 15.75, and the int8 control's gradient alone for
12.75 GB beside 4 GB of weights and gradients (the chip's compiler and a
chip run, PR 28). This driver follows the same model — `lm.init_params`,
the same layer and the same AdamW arithmetic — as `reference/lm_long.py`
has it (attention a block of queries at a time) through
`reference/zaya.follow`, which keeps the Adam moments and the blocks'
running gradient in host memory and updates leaf by leaf. Both are this
reference's constants, not a workload's choice: nothing else fits at 8k.

Everything else is `drivers/train.py`'s: a private copy of that module is
loaded and its `run_reference` rebound, so its `run()` — and `limits.py`,
which calls this module's — reach the reference below.
"""

from __future__ import annotations

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_driver_train_long_base", pathlib.Path(__file__).with_name("train.py")
)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

WORKLOAD_REQUIRED = _base.WORKLOAD_REQUIRED
WORKLOAD_KEYS = _base.WORKLOAD_KEYS
CONFIG_REQUIRED, CONFIG_KEYS = _base.CONFIG_REQUIRED, _base.CONFIG_KEYS
preload, build, first_steps = _base.preload, _base.build, _base.first_steps
gaps, compare, run = _base.gaps, _base.compare, _base.run


def run_reference(cell, key, numbers, feed, devices, quant=None) -> dict:
    """The plain reference over the first three batches, on one chip."""
    from benchmarks.reference import lm_long, zaya

    if cell["chips"] != 1:
        raise ValueError("the long reference is placed on one chip only")
    work = cell["workload"]
    return zaya.follow(
        key, numbers, work["optimizer"], [feed.batch_at(i) for i in range(3)],
        rows_per_block=work.get("reference_rows_per_block", 1), quant=quant,
        model=lm_long,
    )


_base.run_reference = run_reference
