"""Weights from a seed, made on the device in one jitted call.

A configuration's file gives `initializer`: a list of rules, the first
whose `match` (a regular expression, searched in the parameter's bare
name) fits decides the leaf:

  {"match": "gamma$", "fill": 1.0}
  {"match": "weight$", "normal": "xavier_in", "magnitude": 2.0}
      std = sqrt(magnitude / fan_in), fan_in = prod(shape[1:])
      (MXNet's Xavier(rnd_type="gaussian", factor_type="in"))
  {"match": "weight$", "normal": "sigma", "sigma": 0.02}

The program and the plain reference are both handed what this makes; the
reference takes nothing that the program has made.
"""
import math
import re

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _rule_for(name, rules):
    for rule in rules:
        if re.search(rule["match"], name):
            return rule
    raise ValueError("no initializer rule matches parameter %r" % name)


def _std(rule, shape):
    if rule["normal"] == "sigma":
        return float(rule["sigma"])
    if rule["normal"] == "xavier_in":
        return math.sqrt(float(rule["magnitude"]) / math.prod(shape[1:]))
    raise ValueError("unknown normal rule %r" % rule["normal"])


def make_weights(shapes, rules, seed, device=None):
    """{bare name: float32 array} for {bare name: shape}; one program."""
    names = sorted(shapes)
    plan = [(n, tuple(shapes[n]), _rule_for(n, rules)) for n in names]

    def gen(key):
        out = {}
        for i, (name, shape, rule) in enumerate(plan):
            if "fill" in rule:
                out[name] = jnp.full(shape, rule["fill"], jnp.float32)
            else:
                k = jax.random.fold_in(key, i)
                out[name] = _std(rule, shape) * jax.random.normal(
                    k, shape, jnp.float32)
        return out

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(gen)(key)
