"""Model helpers: checkpoint I/O, kvstore wiring, BatchEndParam.

Reference: python/mxnet/model.py (_create_kvstore :55, _initialize_kvstore,
_update_params_on_kvstore :145, _update_params :157, save_checkpoint :384,
load_checkpoint :414, BatchEndParam).
"""
from __future__ import annotations

from collections import namedtuple

from .base import MXNetError
from . import ndarray as nd
from . import symbol as sym
from . import kvstore as kvs

__all__ = ["BatchEndParam", "save_checkpoint", "load_checkpoint",
           "load_params"]

BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])


def _create_kvstore(kvstore, num_device, arg_params):
    """Create kvstore from str/instance (reference: model.py:55)."""
    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, kvs.KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            kv = None
        else:
            kv = kvs.create(kvstore)
            if kvstore == "local":
                max_size = max(p.size for p in arg_params.values())
                if max_size > 1024 * 1024 * 16:
                    update_on_kvstore = False
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    if kv is None:
        update_on_kvstore = False
    return (kv, update_on_kvstore)


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names,
                        update_on_kvstore):
    """Init kvstore keys from params (reference: model.py:105)."""
    for idx, param_on_devs in enumerate(param_arrays):
        name = param_names[idx]
        kvstore.init(name, arg_params[name])
        if update_on_kvstore:
            kvstore.pull(name, param_on_devs, priority=-idx)


def _update_params_on_kvstore(param_arrays, grad_arrays, kvstore,
                              param_names):
    """Push grads, pull updated weights (reference: model.py:145).

    The whole parameter set goes through one batched push_all/pull_all
    pair so a dist kvstore can fuse the gradients into buckets and
    issue one collective per bucket (parallel/bucketing.py) instead of
    one per parameter."""
    names, args, grads, prios = [], [], [], []
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg_list, grad_list = pair
        if grad_list is None or (isinstance(grad_list, list)
                                 and grad_list[0] is None):
            continue
        names.append(param_names[index])
        args.append(arg_list)
        grads.append(grad_list)
        prios.append(-index)
    if not names:
        return
    kvstore.push_all(names, grads, priorities=prios)
    kvstore.pull_all(names, args, priorities=prios)


def _update_params(param_arrays, grad_arrays, updater, num_device,
                   kvstore=None, param_names=None):
    """Local updater path (reference: model.py:157). The optional
    kvstore reduce batches the whole gradient set like
    `_update_params_on_kvstore` does.

    Fused one-program step (docs/performance.md "Fused train step &
    ZeRO-1", default on): with a single logical device the kvstore
    reduce and the optimizer update fuse into ONE donated jit
    program (parallel/fused_step.py) — this is `Module.fit`'s update
    half, so a fit step becomes forward+backward (one executor
    program) plus exactly one exchange+update program. Whether it runs
    is `fused_step.step`'s answer (optimizer class, store, key set); a
    refusal takes the staged push_all/pull_all + update_all path
    below, which remains the bit-parity oracle."""
    updates = [[] for _ in range(num_device)]
    names, kv_grads, prios = [], [], []
    for i, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg_list, grad_list = pair
        if not isinstance(arg_list, (list, tuple)):
            arg_list, grad_list = [arg_list], [grad_list]
        if grad_list[0] is None:
            continue
        index = i
        if kvstore:
            names.append(param_names[index])
            kv_grads.append(grad_list)
            prios.append(-index)
        for k, p in enumerate(zip(arg_list, grad_list)):
            w, g = p
            updates[k].append((index * num_device + k, g, w))
    if num_device == 1:
        from .parallel import fused_step as _fstep
        if _fstep.step(updater, [u[0] for u in updates[0]],
                       [u[1] for u in updates[0]],
                       [u[2] for u in updates[0]],
                       kvstore=kvstore or None):
            return
    if kvstore and names:
        kvstore.push_all(names, kv_grads, priorities=prios)
        kvstore.pull_all(names, kv_grads, priorities=prios)
    for dev_updates in updates:
        if not dev_updates:
            continue
        if hasattr(updater, "update_all"):
            # whole set in one call: FusedUpdater groups it into a few
            # donated jit updates (parallel/fused_update.py)
            updater.update_all([u[0] for u in dev_updates],
                               [u[1] for u in dev_updates],
                               [u[2] for u in dev_updates])
        else:
            for i, g, w in dev_updates:
                updater(i, g, w)


def save_checkpoint(prefix, epoch, symbol, arg_params, aux_params):
    """Save symbol + params (reference: model.py:384). File formats match
    the reference's layout: prefix-symbol.json + prefix-####.params."""
    if symbol is not None:
        symbol.save("%s-symbol.json" % prefix)
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    param_name = "%s-%04d.params" % (prefix, epoch)
    nd.save(param_name, save_dict)


def load_checkpoint(prefix, epoch):
    """Load symbol + params (reference: model.py:414)."""
    symbol = sym.load("%s-symbol.json" % prefix)
    arg_params, aux_params = load_params(prefix, epoch)
    return (symbol, arg_params, aux_params)


def load_params(prefix, epoch):
    save_dict = nd.load("%s-%04d.params" % (prefix, epoch))
    arg_params = {}
    aux_params = {}
    for k, v in save_dict.items():
        tp, name = k.split(":", 1)
        if tp == "arg":
            arg_params[name] = v
        if tp == "aux":
            aux_params[name] = v
    return (arg_params, aux_params)


class FeedForward:
    """Legacy training API (reference: model.py:555 FeedForward).

    Deprecated in the reference in favor of Module; provided for API
    parity and implemented as a thin driver over mxnet_tpu.module.Module.
    """

    def __init__(self, symbol, ctx=None, num_epoch=None, epoch_size=None,
                 optimizer="sgd", initializer=None, numpy_batch_size=128,
                 arg_params=None, aux_params=None, allow_extra_params=False,
                 begin_epoch=0, **kwargs):
        from . import initializer as _init
        self.symbol = symbol
        self.ctx = ctx
        self.num_epoch = num_epoch
        self.epoch_size = epoch_size
        self.optimizer = optimizer
        self.initializer = initializer if initializer is not None \
            else _init.Uniform(0.01)
        self.numpy_batch_size = numpy_batch_size
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.allow_extra_params = allow_extra_params
        self.begin_epoch = begin_epoch
        self.kwargs = kwargs.copy()
        self._module = None

    def _as_iter(self, X, y=None, is_train=True):
        from .io import DataIter, NDArrayIter
        if isinstance(X, DataIter):
            return X
        bs = min(self.numpy_batch_size, len(X))
        return NDArrayIter(X, y, batch_size=bs, shuffle=is_train)

    def fit(self, X, y=None, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None,
            kvstore="local", logger=None, work_load_list=None, monitor=None,
            eval_end_callback=None, eval_batch_end_callback=None):
        """Train the model (reference: model.py:744)."""
        from .module import Module
        data = self._as_iter(X, y)
        label_names = [d.name for d in (data.provide_label or [])] or None
        self._module = Module(self.symbol, label_names=label_names,
                              context=self.ctx)
        self._module.fit(
            data, eval_data=eval_data, eval_metric=eval_metric,
            epoch_end_callback=epoch_end_callback,
            batch_end_callback=batch_end_callback, kvstore=kvstore,
            optimizer=self.optimizer,
            optimizer_params=self.kwargs or {"learning_rate": 0.01},
            initializer=self.initializer,
            arg_params=self.arg_params, aux_params=self.aux_params,
            begin_epoch=self.begin_epoch,
            num_epoch=self.num_epoch or 1, monitor=monitor,
            eval_end_callback=eval_end_callback,
            eval_batch_end_callback=eval_batch_end_callback)
        self.arg_params, self.aux_params = self._module.get_params()
        return self

    def _ensure_module(self, data, for_training=False):
        if self._module is None:
            from .module import Module
            label_names = [d.name for d in
                           (data.provide_label or [])] or None
            self._module = Module(self.symbol, label_names=label_names,
                                  context=self.ctx)
            self._module.bind(data_shapes=data.provide_data,
                              label_shapes=data.provide_label or None,
                              for_training=for_training)
            self._module.set_params(self.arg_params or {},
                                    self.aux_params or {})
        return self._module

    def predict(self, X, num_batch=None, return_data=False, reset=True):
        """Run prediction (reference: model.py:630). With
        return_data=True also returns the consumed data and labels."""
        data = self._as_iter(X, is_train=False)
        mod = self._ensure_module(data)
        if reset:
            data.reset()
        if not return_data:
            outs = mod.predict(data, num_batch=num_batch)
            if isinstance(outs, list):
                return [o.asnumpy() for o in outs]
            return outs.asnumpy()
        outputs, datas, labels = [], [], []
        for i, batch in enumerate(data):
            if num_batch is not None and i >= num_batch:
                break
            mod.forward(batch, is_train=False)
            outputs.append(mod.get_outputs()[0].asnumpy())
            datas.append(batch.data[0].asnumpy())
            labels.append(batch.label[0].asnumpy()
                          if batch.label else None)
        import numpy as _npmod
        return (_npmod.concatenate(outputs), _npmod.concatenate(datas),
                _npmod.concatenate(labels)
                if labels and labels[0] is not None else None)

    def score(self, X, eval_metric="acc", num_batch=None,
              batch_end_callback=None, reset=True):
        """Evaluate; returns the metric value (reference: model.py:673)."""
        data = self._as_iter(X, is_train=False)
        mod = self._ensure_module(data)
        res = list(mod.score(data, eval_metric, num_batch=num_batch))
        # Module.score keys by the metric's display name; return the
        # value (single metric) or the name->value dict (composite)
        if len(res) == 1:
            return res[0][1]
        return dict(res)

    def save(self, prefix, epoch=None):
        """Checkpoint model (reference: model.py:964)."""
        if epoch is None:
            epoch = self.num_epoch or 0
        save_checkpoint(prefix, epoch, self.symbol,
                        self.arg_params or {}, self.aux_params or {})

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        """Load a checkpointed model (reference: model.py:996)."""
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(symbol, ctx=ctx, arg_params=arg_params,
                           aux_params=aux_params, begin_epoch=epoch,
                           **kwargs)

    @staticmethod
    def create(symbol, X, y=None, ctx=None, num_epoch=None,
               epoch_size=None, optimizer="sgd", initializer=None,
               eval_data=None, eval_metric="acc", epoch_end_callback=None,
               batch_end_callback=None, kvstore="local", logger=None,
               work_load_list=None, eval_end_callback=None,
               eval_batch_end_callback=None, **kwargs):
        """Create+train in one call (reference: model.py:1031)."""
        model = FeedForward(symbol, ctx=ctx, num_epoch=num_epoch,
                            epoch_size=epoch_size, optimizer=optimizer,
                            initializer=initializer, **kwargs)
        model.fit(X, y, eval_data=eval_data, eval_metric=eval_metric,
                  epoch_end_callback=epoch_end_callback,
                  batch_end_callback=batch_end_callback, kvstore=kvstore,
                  logger=logger, work_load_list=work_load_list,
                  eval_end_callback=eval_end_callback,
                  eval_batch_end_callback=eval_batch_end_callback)
        return model


__all__.append("FeedForward")
