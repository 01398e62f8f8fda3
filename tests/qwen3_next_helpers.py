"""What the four `test_qwen3_next_*.py` files share: the benchmark's
directories on the path (the tiny configuration and the plain reference
live there), the precision the plain forms are held at, and the
comparisons."""
import os
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

HI = jax.default_matmul_precision("highest")


def _randn(seed, *shapes):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s) for k, s in zip(keys, shapes)]


def _value_and_grads(fn, args):
    """(fn(*args), the gradients of sum(sin(fn)) in every argument), each
    one compiled program."""
    n = tuple(range(len(args)))
    return (jax.jit(fn)(*args),
            jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))), n))(*args))


def _close(a, b, tol):
    scale = max(float(jnp.abs(b).max()), 1e-6)
    assert float(jnp.abs(a - b).max()) <= tol * scale, (
        float(jnp.abs(a - b).max()), scale)


def _products(jaxpr, inside=False):
    """Every `dot_general` inside a `pallas_call`, through the nested
    programs."""
    for eqn in jaxpr.eqns:
        if inside and eqn.primitive.name == "dot_general":
            yield eqn
        within = inside or eqn.primitive.name == "pallas_call"
        for v in eqn.params.values():
            for p in (v if isinstance(v, (list, tuple)) else [v]):
                p = getattr(p, "jaxpr", p)
                if hasattr(p, "eqns"):
                    yield from _products(p, within)
