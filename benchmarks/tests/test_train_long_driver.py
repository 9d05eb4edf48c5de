"""The `train_long` driver is `drivers/train.py` with another placement of
the same reference: at the tiny size both references give the same
numbers, so a cell of either kind is held to the same arithmetic."""

import json
import pathlib

from benchmarks.lib import loader

DATA = pathlib.Path(__file__).parent / "data"


def test_the_long_reference_follows_lm_follow_to_rounding():
    import jax

    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    cell = loader.load_cell("tiny-lm.train", bench, base=DATA, root=DATA)
    short, long = cell["driver"], loader.load_driver("train_long")
    assert long.run is not short.run and long.WORKLOAD_KEYS == short.WORKLOAD_KEYS
    _, feed, key, numbers = short.build(cell, 5, jax.devices())
    want = short.run_reference(cell, key, numbers, feed, jax.devices())
    got = long.run_reference(cell, key, numbers, feed, jax.devices())
    read = short.gaps(got, want)
    assert read["loss"] < 1e-6 and read["first_grad_norm"] < 1e-5
    assert read["change_norm"] < 1e-4
