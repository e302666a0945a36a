"""The comparison that decides `correct`: every number beside its limit."""
import math

import numpy as np


class Checks:
    """Collects (name, value, limit) and prints each; `ok` is their and."""

    def __init__(self):
        self.rows = []

    def at_most(self, name, value, limit):
        value = float(value)
        ok = math.isfinite(value) and value <= limit
        self.rows.append((name, value, limit, ok))
        print(f'check {name}: {value:.6g} (limit {limit:g}) '
              f'{"ok" if ok else "FAILED"}', flush=True)
        return ok

    def true(self, name, cond):
        self.rows.append((name, float(bool(cond)), 1.0, bool(cond)))
        print(f'check {name}: {"ok" if cond else "FAILED"}', flush=True)
        return bool(cond)

    @property
    def ok(self):
        return bool(self.rows) and all(r[3] for r in self.rows)


def rel_l2(a, b):
    """||a - b|| / ||b|| over whole arrays."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def worst_leaf_gap(prog, ref):
    """The largest gap between the program's and the reference's norm of a
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero). Returns
    (gap, leaf)."""
    med = float(np.median([v for v in ref.values()]))
    worst, where = 0.0, None
    for name, r in ref.items():
        gap = abs(prog[name] - r) / max(r, med, 1e-30)
        if not math.isfinite(gap):
            return float('inf'), name
        if gap > worst:
            worst, where = gap, name
    return worst, where
