"""The comparisons that decide ``correct``: pure numpy, no program."""

from __future__ import annotations

import numpy as np

ADAM_B1 = 0.9
# A leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: left out of the change.
TINY_GRADIENT = 1e-3


def host_norms(tree: dict, minus: dict | None = None) -> dict[str, float]:
    """Leaf norms of a stacked host tree (``blocks`` layer by layer), or of
    its difference from another, as ``{"blocks.3.wq": x, ...}``."""
    def norm(a, b, axes):
        d = a if b is None else a - b
        return np.sqrt(np.sum(np.square(d, dtype=np.float64), axis=axes))

    other = (lambda k: None) if minus is None else minus.get
    out = {k: norm(v, other(k), None) for k, v in tree.items()
           if k != "blocks"}
    out["blocks"] = {
        k: norm(v, None if minus is None else minus["blocks"][k],
                tuple(range(1, v.ndim)))
        for k, v in tree["blocks"].items()}
    return flatten_norms(out)


def flatten_norms(norms: dict) -> dict[str, float]:
    """``{"embed": x, "blocks": {"wq": [L], ...}}`` (the stacked form)
    or ``{"embed": x, "blocks": [{"wq": x, ...}, ...]}`` (the program's)
    as ``{"blocks.3.wq": x, ...}``."""
    out = {}
    for key, val in norms.items():
        if key != "blocks":
            out[key] = float(val)
    blocks = norms["blocks"]
    if isinstance(blocks, dict):
        for key, per_layer in blocks.items():
            for i, x in enumerate(np.asarray(per_layer)):
                out[f"blocks.{i}.{key}"] = float(x)
    else:
        for i, blk in enumerate(blocks):
            for key, x in blk.items():
                out[f"blocks.{i}.{key}"] = float(x)
    return out


def leaf_gaps(program: dict[str, float], reference: dict[str, float],
              leaves=None) -> dict[str, float]:
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    names = sorted(reference) if leaves is None else sorted(leaves)
    floor = float(np.median([reference[n] for n in sorted(reference)]))
    return {n: abs(program[n] - reference[n]) / max(reference[n], floor)
            for n in names}


def worst_leaf_gap(program: dict[str, float], reference: dict[str, float],
                   leaves=None) -> tuple[float, str]:
    """The widest of :func:`leaf_gaps`, and the leaf that shows it."""
    if set(program) != set(reference):
        return float("inf"), "leaf sets differ"
    worst, at = 0.0, ""
    for n, gap in leaf_gaps(program, reference, leaves).items():
        if not gap <= worst:  # also catches nan
            worst, at = gap, n
    return float(worst), at


def median_leaf_gap(program, reference, leaves=None) -> float:
    if set(program) != set(reference):
        return float("inf")
    return float(np.median(list(
        leaf_gaps(program, reference, leaves).values())))


def moved_leaves(reference_gradient: dict[str, float]) -> list[str]:
    floor = TINY_GRADIENT * float(np.median(list(reference_gradient.values())))
    return [n for n, g in reference_gradient.items() if g >= floor]


def train_numbers(program: dict, reference: dict) -> dict[str, float]:
    """The numbers a training cell compares. Both sides give ``losses``
    (one per followed step), ``gradient_tree`` (the first gradient as the
    optimizer got it, a stacked host tree) and ``change`` (leaf norms of
    parameters after the followed steps minus parameters before them)."""
    out = {}
    program = dict(program, gradient=host_norms(program["gradient_tree"]))
    reference = dict(reference,
                     gradient=host_norms(reference["gradient_tree"]))
    apart = host_norms(program["gradient_tree"], reference["gradient_tree"])
    floor = float(np.median(list(reference["gradient"].values())))
    worst = max(apart, key=lambda n: apart[n] / max(
        reference["gradient"][n], floor))
    out["gradient_diff_rel"] = apart[worst] / max(
        reference["gradient"][worst], floor)
    out["gradient_diff_rel_at"] = worst
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss_step{i + 1}_rel"] = abs(a - b) / abs(b)
    out["gradient_norm_gap"], out["gradient_norm_gap_at"] = worst_leaf_gap(
        program["gradient"], reference["gradient"])
    moved = moved_leaves(reference["gradient"])
    out["change_norm_gap"], out["change_norm_gap_at"] = worst_leaf_gap(
        program["change"], reference["change"], moved)
    out["gradient_median_gap"] = median_leaf_gap(
        program["gradient"], reference["gradient"])
    out["change_median_gap"] = median_leaf_gap(
        program["change"], reference["change"], moved)
    return out


def checked_from(numbers: dict, limits: dict) -> dict:
    """Each limited number beside its limit, in the limits' order."""
    return {name: {"value": numbers.get(name), "limit": limit}
            for name, limit in limits.items()}
