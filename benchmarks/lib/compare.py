"""The comparison that decides `correct`: numbers, each beside its limit."""

from __future__ import annotations

import statistics


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's — not the norm of their difference — against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves' gradients are all but zero)."""
    if set(program) != set(reference):
        raise ValueError(
            f"leaves differ: {sorted(set(program) ^ set(reference))[:4]}..."
        )
    floor = statistics.median(reference.values())
    gaps = {
        k: abs(program[k] - reference[k]) / max(reference[k], floor)
        for k in reference
    }
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


class Checks:
    """Each number compared, with its limit; `correct` is all of them."""

    def __init__(self):
        self.rows: list[dict] = []

    def at_most(self, what: str, value: float, limit: float, note: str = ""):
        ok = bool(value == value and value <= limit)  # NaN fails
        self.rows.append(
            {"check": what, "value": value, "limit": limit, "ok": ok, "note": note}
        )

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def lines(self) -> list[str]:
        return [
            f"[check] {r['check']}: {r['value']:.6g} (limit {r['limit']:.6g}) "
            f"{'ok' if r['ok'] else 'FAILED'}{' ' + r['note'] if r['note'] else ''}"
            for r in self.rows
        ]
