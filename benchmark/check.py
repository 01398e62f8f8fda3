"""The comparison that decides `correct` for a training cell.

`readings(prog, ref)` turns two sets of {losses, grad_norms,
change_norms} (the program's and the plain reference's, see
reference_train.follow) into the numbers compared; `verdict` holds each
to the limit the cell's file gives it.

  loss_gap_<i>     |L_prog - L_ref| / |L_ref| at step i
  grad_norm_gap    worst leaf of | ||g_prog|| - ||g_ref|| | over
                   max(||g_ref|| of that leaf, of the median leaf)
  change_norm_gap  the same of the parameters' change after the last
                   step, over the leaves whose reference gradient is not
                   under a thousandth of the median leaf's (those move
                   under Adam by round-off alone)
  grad_diff        || g_prog - g_ref || / || g_ref || over every element of
                   every trainable leaf of the first gradient: the norm
                   of the difference, which rounding moves in the first
                   order (a gap of norms moves only in the second, and
                   cannot tell bfloat16 from fp8)
  var_diff         where the model has batch norms: the worst of them by
                   || v_prog - v_ref || / || v_ref || of the batch variance
                   in the first forward pass, which the program's running
                   variance holds after step 1. A sum over every position,
                   it is steady where single activations are not, and the
                   power of a product's rounding noise adds to it;
                   `var_diff_median` is the median batch norm's
  <either>_median    the median leaf's gap in place of the worst leaf's
  <either>_matrices  the worst leaf among those of rank >= 2 (weights of
                     convolutions and matrix products); the per-channel
                     leaves, sums over every position of a bfloat16
                     gradient that all but cancels, are the noisy ones

A norm the program does not report (a leaf it lacks) reads as 0, so the
gap is 1: a leaf left unmoved fails.
"""
import math
import statistics

TINY_GRADIENT = 1e-3


def _squares(prog, ref):
    """{leaf: (|| prog - ref ||^2, || ref ||^2)}, in one program; a leaf
    that `prog` lacks counts as nought."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def squares(a, b):
        f32 = lambda v: jnp.asarray(v, jnp.float32)            # noqa: E731
        return {k: (jnp.sum(jnp.square(f32(a[k]) - f32(b[k]))),
                    jnp.sum(jnp.square(f32(b[k])))) for k in b}

    zero = {k: jnp.zeros_like(v) for k, v in ref.items() if k not in prog}
    got = squares({**{k: prog[k] for k in ref if k in prog}, **zero}, ref)
    return {k: (float(d), float(w)) for k, (d, w) in got.items()}


def grad_diff(prog, ref):
    """|| prog - ref || / || ref || over all leaves of `ref` ({leaf: array})."""
    got = _squares(prog, ref).values()
    return math.sqrt(sum(d for d, _ in got) / sum(w for _, w in got))


def leaf_diffs(prog, ref):
    """{leaf: || prog - ref || / || ref ||}."""
    return {k: math.sqrt(d / max(w, 1e-60))
            for k, (d, w) in _squares(prog, ref).items()}


def _gaps(prog, ref, leaves):
    """{leaf: gap of norms over max(ref's norm of it, of the median leaf)}."""
    floor = statistics.median(ref[k] for k in leaves)
    return {k: abs(prog.get(k, 0.0) - ref[k]) / max(ref[k], floor, 1e-30)
            for k in leaves}


def _worst_leaf(prog, ref, leaves):
    gaps = _gaps(prog, ref, leaves)
    where = max(gaps, key=gaps.get)
    return (gaps[where], where) if gaps[where] > 0 else (0.0, None)


def _median_leaf(prog, ref, leaves):
    return statistics.median(_gaps(prog, ref, leaves).values())


def readings(prog, ref, shapes=None):
    """({name: number}, {name: the leaf that read worst}). `shapes`
    ({leaf: shape}) adds the numbers over the leaves of rank >= 2."""
    out, notes = {}, {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), start=1):
        out["loss_gap_%d" % i] = abs(a - b) / abs(b)
    if len(prog["losses"]) != len(ref["losses"]):
        out["loss_gap_%d" % len(ref["losses"])] = 1.0
    if "grad" in prog and "grad" in ref:
        out["grad_diff"] = grad_diff(prog["grad"], ref["grad"])
    if prog.get("variances") and ref.get("variances"):
        diffs = leaf_diffs(prog["variances"], ref["variances"])
        notes["var_diff"] = max(diffs, key=diffs.get)
        out["var_diff"] = diffs[notes["var_diff"]]
        out["var_diff_median"] = statistics.median(diffs.values())
    leaves = sorted(ref["grad_norms"])
    floor = TINY_GRADIENT * statistics.median(ref["grad_norms"].values())
    moving = [k for k in leaves if ref["grad_norms"][k] >= floor]
    for name, kind, among in (("grad_norm_gap", "grad_norms", leaves),
                              ("change_norm_gap", "change_norms", moving)):
        out[name], notes[name] = _worst_leaf(prog[kind], ref[kind], among)
        out[name + "_median"] = _median_leaf(prog[kind], ref[kind], among)
        if shapes is not None:
            matrices = [k for k in among if len(shapes[k]) >= 2]
            out[name + "_matrices"], notes[name + "_matrices"] = _worst_leaf(
                prog[kind], ref[kind], matrices)
    return out, notes


def verdict(numbers, limits):
    """(correct, [(name, number, limit)]): every limit has its number, and
    a number that is not finite fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and value == value and value <= limit
        ok = ok and good
        rows.append((name, value, limit))
    return ok, rows
