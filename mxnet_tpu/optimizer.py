"""Optimizer zoo.

Reference: python/mxnet/optimizer.py (registry :35,112; SGD :445, Signum
:550, NAG :906, SGLD, Adam :994, AdaGrad :1076, RMSProp :1128, AdaDelta,
Ftrl, Adamax, Nadam, FTML, DCASGD) and the fused C++ update kernels in
src/operator/optimizer_op.cc.

TPU-native design: every update rule is a pure jax function jit-compiled
once per (rule, hyperparam, shape/dtype) signature — the analog of the
reference's fused sgd_update/adam_update kernels, except XLA also fuses
weight-decay/clip/rescale into the same kernel. Multi-precision (fp32
master weights for bf16/fp16 params) mirrors the reference's
multi_precision flag.
"""
from __future__ import annotations

import math
import pickle

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import NDArray
from .observability import registry as _obs

__all__ = ["Optimizer", "SGD", "NAG", "Signum", "SGLD", "Adam", "AdaGrad",
           "RMSProp", "AdaDelta", "Ftrl", "Adamax", "Nadam", "FTML",
           "DCASGD", "LBSGD", "Test", "Updater", "get_updater", "create",
           "register"]

# every optimizer-update computation dispatched to the device: one per
# per-parameter call, one per fused group (parallel/fused_update.py) —
# the per-step delta is how tests assert the O(n_params) -> O(n_groups)
# dispatch drop
_UPDATE_DISPATCHES = _obs.counter(
    "optimizer.update.dispatches",
    "Optimizer update computations dispatched (per-param + fused-group)")
# per-key updates also count toward the step's device-program budget
# (registered+documented in parallel/fused_step.py; name-based here to
# avoid an import cycle)
_STEP_DISPATCHES = _obs.counter("train.step.dispatches")

_KERNEL_JITS = {}


def _jit_update_kernel(name, fn, static_argnums, donate_argnums):
    """One jit a per-op update kernel; jax.jit's own cache handles
    shapes/statics. The weight and the optimizer state are donated
    (never grads, which other code may still read): XLA reuses the
    donated input storage for the same-shaped output, so steady-state
    updates allocate nothing (docs/performance.md aliasing caveat)."""
    jitted = _KERNEL_JITS.get(name)
    if jitted is None:
        jitted = _KERNEL_JITS[name] = jax.jit(
            fn, static_argnums=static_argnums,
            donate_argnums=donate_argnums)
    return jitted


class Optimizer:
    """Base optimizer (reference: optimizer.py:35)."""

    opt_registry = {}

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        if param_idx2name is None:
            param_idx2name = {}
        self.idx2name = param_idx2name.copy()
        self.sym_info = (sym.attr_dict(), sym.list_arguments()) if sym is not None else ()
        self.param_dict = param_dict if param_dict else {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    # -- registry -------------------------------------------------------
    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() not in Optimizer.opt_registry:
            raise MXNetError("cannot find optimizer %s" % name)
        return Optimizer.opt_registry[name.lower()](**kwargs)

    # -- state ----------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        weight_master_copy = None
        if self.multi_precision and weight.dtype in (np.float16,
                                                     np.dtype("bfloat16")):
            weight_master_copy = NDArray(weight._data.astype(jnp.float32))
            return (weight_master_copy, self.create_state(index,
                                                          weight_master_copy))
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def _is_multi_precision_state(self, weight, state):
        """True when `state` is the (fp32 master, base_state) pair
        create_state_multi_precision builds for low-precision weights.
        The dtype checks matter: a tuple-state optimizer (Adam's
        (mean, var)) on fp32 weights is NOT a master/base pair even
        with multi_precision=True — misreading it would unpack mean as
        the master weight. Shared with the fused path
        (parallel/fused_update.py) so both agree on every input."""
        return (self.multi_precision and isinstance(state, tuple)
                and len(state) == 2 and isinstance(state[0], NDArray)
                and state[0]._data.dtype == jnp.float32
                and state[0]._data.dtype != weight._data.dtype)

    def update_multi_precision(self, index, weight, grad, state):
        if self._is_multi_precision_state(weight, state):
            master, base_state = state
            g32 = NDArray(grad._data.astype(jnp.float32))
            self.update(index, master, g32, base_state)
            weight._data = master._data.astype(weight._data.dtype)
        else:
            self.update(index, weight, grad, state)

    # -- lr/wd plumbing (reference: optimizer.py:160-260) ----------------
    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("lr_scheduler is set; cannot set lr directly")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index],
                              self.num_update)

    def _resolved_mult(self, index, attr):
        """The per-index multiplier ('lr_mult' or 'wd_mult') with the
        param_dict -> mult-table -> idx2name resolution chain. The ONE
        copy of the chain: _get_lr/_get_wd scale by it, and the fused
        update (parallel/fused_update.py) uses it as the stable group
        lane identity, so the two can never drift apart."""
        if index in self.param_dict:
            return float(getattr(self.param_dict[index], attr))
        table = getattr(self, attr)
        if index in table:
            return float(table[index])
        if index in self.idx2name:
            return float(table.get(self.idx2name[index], 1.0))
        return 1.0

    def _get_lr(self, index):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        return lr * self._resolved_mult(index, "lr_mult")

    def _get_wd(self, index):
        return self.wd * self._resolved_mult(index, "wd_mult")

    def __getstate__(self):
        d = self.__dict__.copy()
        return d


register = Optimizer.register
create = Optimizer.create_optimizer


def _prep(grad, rescale, clip, wd, weight):
    """Common gradient preprocessing, fused by XLA into the update."""
    g = grad * rescale
    if clip is not None:
        g = jnp.clip(g, -clip, clip)
    if wd:
        g = g + wd * weight
    return g


# Each kernel is jitted per hyper-param + shape signature (scalars passed
# as traced args would defeat constant folding for schedules; lr changes
# per step, so lr IS a traced arg while wd/clip/momentum are static).


def _sgd_math(weight, grad, lr, rescale, clip, wd, momentum, mom=None):
    g = _prep(grad, rescale, clip, wd, weight)
    if momentum:
        mom = momentum * mom - lr * g
        return weight + mom, mom
    return weight - lr * g, None


def _sgd_kernel(*args):
    return _jit_update_kernel("sgd", _sgd_math, (3, 4, 5, 6),
                              (0, 7))(*args)


@register
class SGD(Optimizer):
    """SGD with momentum and optional multi-precision
    (reference: optimizer.py:445, fused kernel optimizer_op.cc sgd_update)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros_like(weight._data))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        if getattr(grad, "stype", "default") == "row_sparse":
            grad = grad.tostype("default")
        # momentum-less updates pass mom=None (an empty pytree): a dummy
        # array would be donated with no matching output and warn
        new_w, new_m = _sgd_kernel(
            weight._data, grad._data, lr, self.rescale_grad,
            self.clip_gradient, wd, self.momentum,
            state._data if state is not None else None)
        weight._data = new_w
        if state is not None and new_m is not None:
            state._data = new_m


@register
class NAG(SGD):
    """Nesterov accelerated SGD (reference: optimizer.py:906)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient, wd,
                  weight._data)
        if state is not None:
            m = state._data
            m = self.momentum * m + g
            g = g + self.momentum * m
            state._data = m
        weight._data = weight._data - lr * g


@register
class Signum(Optimizer):
    """signSGD / Signum (reference: optimizer.py:550)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros_like(weight._data))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad._data * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        if state is not None:
            m = self.momentum * state._data - (1 - self.momentum) * (
                g + wd * weight._data)
            state._data = m
            d = jnp.sign(m)
            weight._data = (1 - lr * self.wd_lh) * weight._data + lr * d
        else:
            weight._data = (1 - lr * (wd + self.wd_lh)) * weight._data \
                - lr * jnp.sign(g)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference: optimizer.py)."""

    def update(self, index, weight, grad, state):
        from . import random as _random
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient, wd,
                  weight._data)
        noise = jax.random.normal(_random.next_key(), weight.shape,
                                  weight._data.dtype) * math.sqrt(lr)
        weight._data = weight._data - lr / 2 * g + noise


def _adam_math(weight, grad, mean, var, lr, beta1, beta2, epsilon,
               rescale, clip, wd, t=1):
    g = _prep(grad, rescale, clip, wd, weight)
    mean = beta1 * mean + (1 - beta1) * g
    var = beta2 * var + (1 - beta2) * jnp.square(g)
    coef1 = 1.0 - beta1 ** t
    coef2 = 1.0 - beta2 ** t
    lr_t = lr * (coef2 ** 0.5) / coef1
    w = weight - lr_t * mean / (jnp.sqrt(var) + epsilon)
    return w, mean, var


def _adam_kernel(*args):
    return _jit_update_kernel("adam", _adam_math, (5, 6, 7, 8, 9, 10),
                              (0, 2, 3))(*args)


@register
class Adam(Optimizer):
    """Adam (reference: optimizer.py:994, adam_update optimizer_op.cc)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        mean, var = state
        w, m, v = _adam_kernel(weight._data, grad._data, mean._data,
                               var._data, lr, self.beta1, self.beta2,
                               self.epsilon, self.rescale_grad,
                               self.clip_gradient, wd, t)
        weight._data = w
        mean._data = m
        var._data = v


# RMSProp/AdaGrad math in the fused-kernel signature
# (w, g, states, lr, t, wd, hyper): the per-key jits below AND the
# fused group jits (parallel/fused_update.py) wrap this SAME function,
# so both paths trace the same expressions (an eager per-key path
# would let XLA make different fusion/FMA choices than the fused
# kernel). Same expressions are the same bits for AdaGrad. For RMSProp
# they are not quite: XLA rewrites `x / sqrt(y)` to `x * rsqrt(y)`, and
# XLA:CPU emits rsqrt as the hardware estimate refined by two Newton
# steps whose multiply-adds it contracts by the loop it is emitting, so
# centered RMSProp over a (n, 4) array and over the same elements in a
# flat buffer can differ by one ulp of the quotient a step (a reshape
# inside the jit does not pin the loop's shape: XLA folds it away).


def _adagrad_math(weight, grad, states, lr, t, wd, hyper):
    epsilon, rescale, clip = hyper
    g = _prep(grad, rescale, clip, wd, weight)
    hist = states[0] + jnp.square(g)
    return weight - lr * g / (jnp.sqrt(hist) + epsilon), (hist,)


def _rmsprop_math(weight, grad, states, lr, t, wd, hyper):
    gamma1, gamma2, epsilon, centered, clip_weights, rescale, clip = hyper
    g = _prep(grad, rescale, clip, wd, weight)
    if centered:
        n, gm, delta = states
        n_ = gamma1 * n + (1 - gamma1) * jnp.square(g)
        gm_ = gamma1 * gm + (1 - gamma1) * g
        d_ = gamma2 * delta - lr * g / jnp.sqrt(
            n_ - jnp.square(gm_) + epsilon)
        w = weight + d_
        new_states = (n_, gm_, d_)
    else:
        (n,) = states
        n_ = (1 - gamma1) * jnp.square(g) + gamma1 * n
        w = weight - lr * g / jnp.sqrt(n_ + epsilon)
        new_states = (n_,)
    if clip_weights:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, new_states


def _adagrad_kernel(*args):
    return _jit_update_kernel("adagrad", _adagrad_math, (5, 6),
                              (0, 2))(*args)


def _rmsprop_kernel(*args):
    return _jit_update_kernel("rmsprop", _rmsprop_math, (5, 6),
                              (0, 2))(*args)


@register
class AdaGrad(Optimizer):
    """AdaGrad (reference: optimizer.py:1076)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return NDArray(jnp.zeros_like(weight._data))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        new_w, (hist,) = _adagrad_kernel(
            weight._data, grad._data, (state._data,), lr,
            self._index_update_count[index], wd,
            (self.float_stable_eps, self.rescale_grad,
             self.clip_gradient))
        state._data = hist
        weight._data = new_w


@register
class RMSProp(Optimizer):
    """RMSProp, centered + vanilla (reference: optimizer.py:1128)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (NDArray(jnp.zeros_like(weight._data)),
                    NDArray(jnp.zeros_like(weight._data)),
                    NDArray(jnp.zeros_like(weight._data)))
        return (NDArray(jnp.zeros_like(weight._data)),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        new_w, new_states = _rmsprop_kernel(
            weight._data, grad._data, tuple(s._data for s in state), lr,
            self._index_update_count[index], wd,
            (self.gamma1, self.gamma2, self.epsilon, self.centered,
             self.clip_weights, self.rescale_grad, self.clip_gradient))
        for s, ns in zip(state, new_states):
            s._data = ns
        weight._data = new_w


@register
class AdaDelta(Optimizer):
    """AdaDelta (reference: optimizer.py)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient, wd,
                  weight._data)
        acc_g, acc_delta = state
        ag = self.rho * acc_g._data + (1 - self.rho) * jnp.square(g)
        delta = jnp.sqrt(acc_delta._data + self.epsilon) / jnp.sqrt(
            ag + self.epsilon) * g
        ad = self.rho * acc_delta._data + (1 - self.rho) * jnp.square(delta)
        acc_g._data, acc_delta._data = ag, ad
        weight._data = weight._data - delta


@register
class Ftrl(Optimizer):
    """FTRL (reference: optimizer.py, ftrl_update optimizer_op.cc)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),   # z
                NDArray(jnp.zeros_like(weight._data)))   # n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad._data * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        z, n = state
        sigma = (jnp.sqrt(n._data + jnp.square(g)) - jnp.sqrt(n._data)) / lr
        z_ = z._data + g - sigma * weight._data
        n_ = n._data + jnp.square(g)
        z._data, n._data = z_, n_
        weight._data = jnp.where(
            jnp.abs(z_) <= self.lamda1,
            jnp.zeros_like(z_),
            (jnp.sign(z_) * self.lamda1 - z_)
            / ((self.beta + jnp.sqrt(n_)) / lr + wd))


@register
class Adamax(Optimizer):
    """AdaMax (reference: optimizer.py)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        lr /= (1.0 - self.beta1 ** t)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient, wd,
                  weight._data)
        m, u = state
        m_ = self.beta1 * m._data + (1 - self.beta1) * g
        u_ = jnp.maximum(self.beta2 * u._data, jnp.abs(g))
        m._data, u._data = m_, u_
        weight._data = weight._data - lr * m_ / (u_ + 1e-8)


@register
class Nadam(Optimizer):
    """Nesterov Adam (reference: optimizer.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient, wd,
                  weight._data)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m, v = state
        g_prime = g / (1.0 - self.m_schedule)
        m_ = self.beta1 * m._data + (1.0 - self.beta1) * g
        v_ = self.beta2 * v._data + (1.0 - self.beta2) * jnp.square(g)
        m_prime = m_ / (1.0 - m_schedule_next)
        v_prime = v_ / (1.0 - self.beta2 ** t)
        m_bar = (1.0 - momentum_t) * g_prime + momentum_t_1 * m_prime
        m._data, v._data = m_, v_
        weight._data = weight._data - lr * m_bar / (
            jnp.sqrt(v_prime) + self.epsilon)


@register
class FTML(Optimizer):
    """FTML (reference: optimizer.py FTML)."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),   # d
                NDArray(jnp.zeros_like(weight._data)),   # v
                NDArray(jnp.zeros_like(weight._data)))   # z

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient, wd,
                  weight._data)
        d, v, z = state
        v_ = self.beta2 * v._data + (1 - self.beta2) * jnp.square(g)
        d_ = (1 - self.beta1 ** t) / lr * (
            jnp.sqrt(v_ / (1 - self.beta2 ** t)) + self.epsilon)
        sigma = d_ - self.beta1 * d._data
        z_ = self.beta1 * z._data + (1 - self.beta1) * g - sigma * weight._data
        d._data, v._data, z._data = d_, v_, z_
        weight._data = -z_ / d_


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer.py:850)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, NDArray(weight._data))
        return (NDArray(jnp.zeros_like(weight._data)), NDArray(weight._data))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient, wd,
                  weight._data)
        mom, prev = state
        comp = g + self.lamda * g * g * (weight._data - prev._data)
        if mom is not None:
            m = self.momentum * mom._data - lr * (comp + wd * weight._data)
            mom._data = m
            step = m
        else:
            step = -lr * (comp + wd * weight._data)
        prev._data = weight._data
        weight._data = weight._data + step


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layer-wise adaptive rate
    (reference: optimizer.py:660)."""

    def __init__(self, warmup_strategy="linear", warmup_epochs=5,
                 batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, **kwargs):
        super().__init__(**kwargs)
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch

    def update(self, index, weight, grad, state):
        # LARS trust ratio
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = _prep(grad._data, self.rescale_grad, self.clip_gradient, wd,
                  weight._data)
        wnorm = jnp.linalg.norm(weight._data)
        gnorm = jnp.linalg.norm(g)
        trust = jnp.where(gnorm > 0, wnorm / (gnorm + 1e-9), 1.0)
        trust = jnp.clip(trust, 0.0, 50.0)
        lr_eff = lr * trust
        if state is not None:
            m = self.momentum * state._data - lr_eff * g
            state._data = m
            weight._data = weight._data + m
        else:
            weight._data = weight._data - lr_eff * g


@register
class Test(Optimizer):
    """Trivial optimizer used by unit tests (reference: optimizer.py Test)."""

    def create_state(self, index, weight):
        return NDArray(jnp.zeros_like(weight._data))

    def update(self, index, weight, grad, state):
        weight._data = weight._data - self.rescale_grad * grad._data


# shorthand aliases the reference exposes
ccSGD = SGD
Optimizer.opt_registry["ccsgd"] = SGD


class Updater:
    """Applies an optimizer keyed by parameter index (reference:
    optimizer.py get_updater / Updater — also what kvstore servers run)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        elif not self.states_synced.get(index, True):
            # states adopted via set_states: align context lazily on
            # first use, like the reference Updater (optimizer.py:1573)
            self.states[index] = self.sync_state_context(
                self.states[index], weight._ctx)
            self.states_synced[index] = True
        _UPDATE_DISPATCHES.inc()
        _STEP_DISPATCHES.inc()
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def update_all(self, indices, grads, weights):
        """Batched update over parallel (index, grad, weight) lists.
        The base implementation is the per-key loop; FusedUpdater
        (parallel/fused_update.py) overrides it with grouped, donated
        single-jit updates. Call sites (Trainer, KVStore, model) hand
        the WHOLE set here so fusion can see it."""
        for i, g, w in zip(indices, grads, weights):
            self(i, g, w)

    def sync_state_context(self, state, context):
        """Recursively re-home optimizer state onto `context`
        (reference: optimizer.py Updater.sync_state_context). Dtypes
        are preserved — in particular fp32 master weights of
        multi-precision states stay fp32."""
        if isinstance(state, NDArray):
            return state.as_in_context(context) if context is not None \
                else state
        if isinstance(state, (list, tuple)):
            return type(state)(self.sync_state_context(s, context)
                               for s in state)
        return state

    def set_states(self, states):
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, self.optimizer = states
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        return pickle.dumps((self.states, self.optimizer)
                            if dump_optimizer else self.states)


def get_updater(optimizer):
    """An updater for kvstore/trainer/module drive loops. Returns the
    fusing variant (parallel/fused_update.py) — it degrades to the
    per-key path per call for unsupported optimizers and sparse keys,
    so it is always a safe default. `Updater(optimizer)` is the
    per-key reference the tests hold it against."""
    try:
        from .parallel.fused_update import FusedUpdater
    except ImportError:
        return Updater(optimizer)
    return FusedUpdater(optimizer)
