"""High-level Model API (reference python/paddle/hapi/model.py:1051 —
Model.prepare/fit/evaluate/predict/save/load/summary).

TPU-native notes: the train/eval batch paths run through the eager engine
(jit-per-op XLA); `prepare(..., jit=True)` additionally compiles the whole
train step into one donated XLA program via jit.TrainStep — the analog of
the reference's `Model` static-graph mode, minus the separate Program
world.
"""

from __future__ import annotations

import os
import time
import warnings
from typing import List, Optional

import numpy as np

from ..core.tensor import Tensor
from ..observability import perf as _perf_mod
from ..metric import Metric
from ..nn.layer_base import Layer
from . import callbacks as cbks_mod

__all__ = ["Model", "summary"]


def _to_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _to_tensor(x):
    if isinstance(x, Tensor):
        return x
    return Tensor(np.asarray(x))


class Model:
    """Network wrapper with train/eval/predict loops (reference Model)."""

    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._inputs = _to_list(inputs)
        self._labels = _to_list(labels)
        self._loss = None
        self._metrics: List[Metric] = []
        self._optimizer = None
        self._train_step = None   # compiled TrainStep when jit=True
        self._captured_step = None  # FLAGS_step_capture auto-capture
        self._multi_step = None   # FLAGS_multi_step K-block auto-capture
        self._jit = False
        self.stop_training = False

    # ------------------------------------------------------------------ mode
    @property
    def mode(self):
        return "train" if self.network.training else "eval"

    def train(self):
        self.network.train()

    def eval(self):
        self.network.eval()

    # --------------------------------------------------------------- prepare
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, jit=False):
        self._optimizer = optimizer
        self._captured_step = None   # new opt/loss: stale capture closure
        self._multi_step = None
        if loss is not None and not (isinstance(loss, Layer)
                                     or callable(loss)):
            raise TypeError("loss must be a Layer or a callable")
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metric {m} is not a paddle_tpu.metric."
                                f"Metric")
        self._jit = bool(jit)
        if amp_configs not in (None, "O0", False):
            self._amp_level = amp_configs if isinstance(amp_configs, str) \
                else amp_configs.get("level", "O1")
        else:
            self._amp_level = None
        return self

    def _loss_value(self, outputs, labels):
        loss = self._loss(*outputs, *labels)
        if isinstance(loss, (list, tuple)):
            loss = loss[0]
        return loss

    # ----------------------------------------------------------- batch steps
    def train_batch(self, inputs, labels=None, update=True):
        assert self._optimizer is not None and self._loss is not None, \
            "call prepare(optimizer, loss) before train_batch"
        self.network.train()
        inputs = [_to_tensor(x) for x in _to_list(inputs)]
        labels = [_to_tensor(x) for x in _to_list(labels)]

        if self._jit and update:
            if self._train_step is None:
                from ..jit.api import TrainStep

                def _scalar_loss(*args):
                    loss = self._loss(*args)
                    if isinstance(loss, (list, tuple)):
                        loss = loss[0]
                    return loss

                self._train_step = TrainStep(self.network, _scalar_loss,
                                             self._optimizer,
                                             amp_level=self._amp_level)
            t0 = time.perf_counter()
            loss = self._train_step(tuple(inputs), tuple(labels))
            t1 = time.perf_counter()
            lv = float(loss._data if isinstance(loss, Tensor) else loss)
            t2 = time.perf_counter()
            # dispatch returns before the device finishes; the float() sync
            # above bounds device time from the host's point of view
            _perf_mod.record_step(t2 - t0, host_s=t1 - t0, device_s=t2 - t1)
            if not self._metrics:
                return self._with_metric_results(None, labels, [lv])
            # metrics need network outputs, which the compiled step does not
            # expose — pay one extra no-grad forward for them, in eval mode
            # so BatchNorm stats / dropout are not perturbed a second time
            from ..autograd.engine import no_grad
            self.network.eval()
            try:
                with no_grad():
                    outputs = _to_list(self.network(*inputs))
            finally:
                self.network.train()
            return self._with_metric_results(outputs, labels, [lv])

        if not update:  # loss/metrics only, no parameter change
            from ..autograd.engine import no_grad
            with no_grad():
                outputs = _to_list(self.network(*inputs))
                loss = self._loss_value(outputs, labels)
            return self._with_metric_results(outputs, labels,
                                             [float(np.asarray(loss._data))])

        # FLAGS_step_capture: after one eager probe the whole eager step
        # (fwd + tape backward + opt.step/clear_grad) replays as ONE
        # donated XLA executable (jit/step_capture.py); outputs come back
        # from the same step, so metrics see the train-mode forward
        # exactly as the eager path does. Unfusable steps transparently
        # run the eager body below via the capture's own fallback.
        from .. import flags as _flags
        if _flags.get_flag("step_capture"):
            if self._captured_step is None:
                from ..jit.step_capture import jit_step
                self._captured_step = jit_step(self._eager_step_fn())
            t0 = time.perf_counter()
            loss, outputs = self._captured_step(tuple(inputs), tuple(labels))
            t1 = time.perf_counter()
            lv = float(np.asarray(loss._data))
            t2 = time.perf_counter()
            _perf_mod.record_step(t2 - t0, host_s=t1 - t0, device_s=t2 - t1)
            return self._with_metric_results(outputs, labels, [lv])

        t0 = time.perf_counter()
        outputs = self._forward_amp(inputs)
        loss = self._loss_value(outputs, labels)
        loss.backward()
        self._optimizer.step()
        self._optimizer.clear_grad()
        t1 = time.perf_counter()
        lv = float(np.asarray(loss._data))
        t2 = time.perf_counter()
        _perf_mod.record_step(t2 - t0, host_s=t1 - t0, device_s=t2 - t1)
        return self._with_metric_results(outputs, labels, [lv])

    def _eager_step_fn(self):
        """The whole-step closure both capture regimes compile: one
        eager step (fwd, tape backward, opt.step/clear_grad) returning
        (loss, outputs). jit_step captures it as-is; jit_step(k_steps=K)
        scans the same body K times."""

        def _eager_step(ins, lbs):
            outputs = self._forward_amp(list(ins))
            loss = self._loss_value(outputs, list(lbs))
            loss.backward()
            self._optimizer.step()
            self._optimizer.clear_grad()
            return loss, outputs

        return _eager_step

    def _forward_amp(self, inputs):
        if self._amp_level:
            from .. import amp as amp_mod
            with amp_mod.auto_cast(level=self._amp_level):
                return _to_list(self.network(*inputs))
        return _to_list(self.network(*inputs))

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        inputs = [_to_tensor(x) for x in _to_list(inputs)]
        labels = [_to_tensor(x) for x in _to_list(labels)]
        from ..autograd.engine import no_grad
        with no_grad():
            outputs = self._forward_amp(inputs)
            metrics = []
            if self._loss is not None and labels:
                loss = self._loss_value(outputs, labels)
                metrics.append(float(np.asarray(loss._data)))
        return self._with_metric_results(outputs, labels, metrics)

    def predict_batch(self, inputs):
        self.network.eval()
        inputs = [_to_tensor(x) for x in _to_list(inputs)]
        from ..autograd.engine import no_grad
        with no_grad():
            outputs = _to_list(self.network(*inputs))
        return [np.asarray(o._data) for o in outputs]

    def _with_metric_results(self, outputs, labels, losses):
        if outputs is None:
            return losses if len(losses) != 1 else losses[0]
        metric_vals = []
        for m in self._metrics:
            computed = m.compute(*outputs, *labels)
            r = m.update(*_to_list(computed))
            metric_vals.append(r)
        if metric_vals:
            return losses, metric_vals
        return losses if len(losses) != 1 else losses[0]

    # ------------------------------------------------------------- data prep
    def _make_loader(self, data, batch_size, shuffle, num_workers, drop_last):
        from ..io import DataLoader, Dataset, IterableDataset
        if data is None:
            return None
        if isinstance(data, DataLoader):
            return data
        if isinstance(data, (Dataset, IterableDataset)):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers, drop_last=drop_last)
        return data  # any iterable of batches

    @staticmethod
    def _split_batch(batch, n_labels):
        batch = _to_list(batch)
        if n_labels and len(batch) > n_labels:
            return batch[:-n_labels], batch[-n_labels:]
        if len(batch) >= 2:
            return batch[:-1], batch[-1:]
        return batch, []

    # ------------------------------------------------------------------- fit
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            resilience_dir=None, snapshot_steps=100):
        assert train_data is not None, "train_data must be given"
        if resilience_dir:
            # preemption-safe auto-checkpointing: async snapshots every
            # `snapshot_steps` batches + restore-on-start from the newest
            # COMMITTED generation (distributed/resilience)
            callbacks = _to_list(callbacks) + [cbks_mod.ResilientCheckpoint(
                resilience_dir, snapshot_steps=snapshot_steps)]
        loader = self._make_loader(train_data, batch_size, shuffle,
                                   num_workers, drop_last)
        eval_loader = self._make_loader(eval_data, batch_size, False,
                                        num_workers, False)
        steps = len(loader) if hasattr(loader, "__len__") else None
        metric_names = ["loss"] + [n for m in self._metrics
                                   for n in _to_list(m.name())]
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps,
            log_freq=log_freq, verbose=verbose, save_freq=save_freq,
            save_dir=save_dir, metrics=metric_names)
        self.stop_training = False
        k_steps = self._multi_k(loader, cbks)
        if k_steps:
            for c in cbks:
                if isinstance(c, cbks_mod.ResilientCheckpoint):
                    # snapshots land on K-block boundaries only, and the
                    # loader's committed ring cursor rides host_state —
                    # a mid-K-block preemption resumes byte-identically
                    c.block_steps = k_steps
                    c.attach_data_stream(loader)
        cbks.on_train_begin()
        n_labels = len(self._labels)
        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch)
            for m in self._metrics:
                m.reset()
            logs = {}
            if k_steps:
                logs = self._fit_epoch_multi(loader, cbks, n_labels,
                                             k_steps, logs)
            else:
                for step, batch in enumerate(_perf_mod.timed_iter(loader)):
                    cbks.on_train_batch_begin(step)
                    ins, lbs = self._split_batch(batch, n_labels)
                    res = self.train_batch(ins, lbs)
                    logs = self._update_logs(res)
                    cbks.on_train_batch_end(step, logs)
                    if self.stop_training:
                        break
            cbks.on_epoch_end(epoch, logs)
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                self._run_eval(eval_loader, cbks, n_labels)
            if self.stop_training:
                break
        cbks.on_train_end(logs)
        return self

    def _update_logs(self, res):
        logs = {}
        if isinstance(res, tuple) and len(res) == 2 \
                and isinstance(res[0], list):
            losses, metric_vals = res
            logs["loss"] = losses[0] if losses else None
            for m, v in zip(self._metrics, metric_vals):
                names = _to_list(m.name())
                vals = _to_list(m.accumulate())
                for n, vv in zip(names, vals):
                    logs[n] = vv
        elif isinstance(res, list):
            if res:
                logs["loss"] = res[0]
        else:
            logs["loss"] = res
        return logs

    # ------------------------------------------------- multi-step (K-blocks)
    def _multi_k(self, loader, cbks) -> int:
        """K when FLAGS_multi_step can drive this fit in K-step blocks,
        else 0. Edges that need per-step host dispatch fall back to the
        single-step loop with a frozen reason in the flight recorder."""
        from .. import flags as _flags
        k = int(_flags.get_flag("multi_step"))
        if k <= 1 or self._jit or not _flags.get_flag("step_capture"):
            return 0
        from ..io import DataLoader, IterableDataset
        from ..jit.multi_step import record_block_fallback
        if not isinstance(loader, DataLoader) \
                or isinstance(loader.dataset, IterableDataset):
            record_block_fallback(
                "ring block shorter than k_steps (epoch tail)",
                "train_data is not a map-style DataLoader — no "
                "resumable ring to fill; whole run is a tail")
            return 0
        unsafe = self._multi_unsafe_reason(cbks)
        if unsafe:
            record_block_fallback(
                "per-step host callbacks need single-step dispatch",
                unsafe)
            return 0
        return k

    def _multi_unsafe_reason(self, cbks) -> Optional[str]:
        """Blocks run K steps before ANY host hook fires; the per-step
        callbacks are then replayed post-hoc in order. That is safe for
        read-only observers, but a hook that MUTATES training state
        between steps (a by_step schedule, a custom hook) would see —
        and steer — a different run than single-step dispatch."""
        for c in cbks:
            if isinstance(c, cbks_mod.LRScheduler):
                if c.by_step:
                    return (f"{type(c).__name__}(by_step=True) steps the "
                            f"schedule between captured steps")
                continue
            if isinstance(c, (cbks_mod.ProgBarLogger,
                              cbks_mod.ResilientCheckpoint)):
                continue   # read-only / block-aligned: post-hoc safe
            if type(c).on_train_batch_begin is not \
                    cbks_mod.Callback.on_train_batch_begin \
                    or type(c).on_train_batch_end is not \
                    cbks_mod.Callback.on_train_batch_end:
                return f"{type(c).__name__} overrides per-step batch hooks"
        return None

    def _fit_epoch_multi(self, loader, cbks, n_labels, k, logs):
        """One epoch in K-step blocks: the DataLoader prefetch thread
        hands over [K, ...]-stacked RingBlocks, ONE scanned executable
        trains each block, the loader's committed stream state advances
        to the block boundary, and only then do the per-step callbacks
        replay — paired, in order, with the block's [K]-stacked losses
        read back once. The K-misaligned epoch tail runs through the
        existing single-step capture."""
        from ..jit.multi_step import multi_counters
        rcs = [c for c in cbks if isinstance(c, cbks_mod.ResilientCheckpoint)]

        def blocks():
            n = 0
            for b in loader.fill_ring(k):
                n += 1
                yield b
            if n == 0:
                # a restored cursor can sit EXACTLY on an epoch
                # boundary — one empty resumed pass is legal, roll
                # straight into the next epoch (run_data's rule)
                for b in loader.fill_ring(k):
                    yield b

        step = 0
        for block in _perf_mod.timed_iter(blocks()):
            if block.stacked is not None:
                losses, outputs, lbs = self._train_block(block.stacked,
                                                         n_labels, k)
                loader._commit_stream_state(block.stream_state)
                for i in range(block.size):
                    for c in rcs:   # snapshots only at block-final steps
                        c._mid_block = i < block.size - 1
                    cbks.on_train_batch_begin(step)
                    if self._metrics and outputs:
                        res = self._with_metric_results(
                            [Tensor(o._data[i]) for o in outputs],
                            [Tensor(y._data[i]) for y in lbs],
                            [losses[i]])
                    else:
                        res = losses[i]
                    logs = self._update_logs(res)
                    cbks.on_train_batch_end(step, logs)
                    step += 1
                    if self.stop_training:
                        break
            else:
                for c in rcs:   # tail steps are ordinary single steps
                    c._mid_block = False
                for batch in block.batches:
                    cbks.on_train_batch_begin(step)
                    ins, lbs = self._split_batch(batch, n_labels)
                    res = self.train_batch(ins, lbs)
                    loader._commit_stream_state(block.stream_state)
                    logs = self._update_logs(res)
                    multi_counters["tail_steps"] += 1
                    cbks.on_train_batch_end(step, logs)
                    step += 1
                    if self.stop_training:
                        break
            if self.stop_training:
                break
        return logs

    def _train_block(self, stacked, n_labels, k):
        """Train one [K, ...]-stacked block through the K-step scanned
        executable. Returns (per-step float losses, [K]-stacked output
        Tensors, [K]-stacked label Tensors) — the latter two feed the
        post-hoc per-step metric updates by slicing, no extra forward."""
        assert self._optimizer is not None and self._loss is not None, \
            "call prepare(optimizer, loss) before fit"
        self.network.train()
        ins, lbs = self._split_batch(stacked, n_labels)
        ins = [_to_tensor(x) for x in ins]
        lbs = [_to_tensor(x) for x in lbs]
        if self._multi_step is None or self._multi_step.k_steps != k:
            from ..jit.step_capture import jit_step
            self._multi_step = jit_step(self._eager_step_fn(), k_steps=k)
        t0 = time.perf_counter()
        loss, outputs = self._multi_step(tuple(ins), tuple(lbs))
        t1 = time.perf_counter()
        losses = [float(v) for v in np.asarray(loss._data)]
        t2 = time.perf_counter()
        # one observation per block
        _perf_mod.record_step(t2 - t0, host_s=t1 - t0, device_s=t2 - t1)
        return losses, _to_list(outputs), lbs

    def _run_eval(self, eval_loader, cbks, n_labels):
        cbks.on_eval_begin()
        for m in self._metrics:
            m.reset()
        logs = {}
        loss_sum, loss_n = 0.0, 0
        for step, batch in enumerate(eval_loader):
            cbks.on_eval_batch_begin(step)
            ins, lbs = self._split_batch(batch, n_labels)
            res = self.eval_batch(ins, lbs)
            logs = self._update_logs(res)
            if "loss" in logs:
                loss_sum += logs["loss"]
                loss_n += 1
            cbks.on_eval_batch_end(step, logs)
        if loss_n:  # epoch-mean loss, not last-batch (monitored by
            logs["loss"] = loss_sum / loss_n  # EarlyStopping/ReduceLR)
        cbks.on_eval_end(logs)
        return logs

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None):
        loader = self._make_loader(eval_data, batch_size, False, num_workers,
                                   False)
        metric_names = ["loss"] + [n for m in self._metrics
                                   for n in _to_list(m.name())]
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, log_freq=log_freq, verbose=verbose,
            metrics=metric_names, mode="eval",
            steps=len(loader) if hasattr(loader, "__len__") else None)
        return self._run_eval(loader, cbks, len(self._labels))

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = self._make_loader(test_data, batch_size, False, num_workers,
                                   False)
        cbks = cbks_mod.config_callbacks(callbacks, model=self,
                                         verbose=verbose, mode="predict")
        cbks.on_predict_begin()
        outputs = []
        for step, batch in enumerate(loader):
            cbks.on_predict_batch_begin(step)
            ins = _to_list(batch)
            # predict data may still carry labels: keep declared inputs if
            # specs were given, else trim to the network's positional arity
            if self._inputs:
                ins = ins[:len(self._inputs)]
            elif self._labels:
                ins, _ = self._split_batch(batch, len(self._labels))
            else:
                ins = ins[:self._forward_arity(len(ins))]
            out = self.predict_batch(ins)
            outputs.append(out)
            cbks.on_predict_batch_end(step, {})
        cbks.on_predict_end()
        if stack_outputs and outputs:
            n_out = len(outputs[0])
            return [np.concatenate([b[i] for b in outputs], axis=0)
                    for i in range(n_out)]
        return outputs

    def _forward_arity(self, have: int) -> int:
        """How many of `have` batch elements the network's forward can
        take positionally (*args -> all of them)."""
        import inspect
        try:
            sig = inspect.signature(self.network.forward)
        except (TypeError, ValueError):
            return have
        n = 0
        for p in sig.parameters.values():
            if p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD):
                return have
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
                n += 1
        return min(have, n)

    # ------------------------------------------------------------- save/load
    def save(self, path, training=True):
        from ..framework import save as fsave
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        fsave(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            fsave(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework import load as fload
        params = fload(path + ".pdparams")
        if skip_mismatch:
            own = self.network.state_dict()
            params = {k: v for k, v in params.items()
                      if k in own and tuple(np.shape(v)) ==
                      tuple(own[k].shape)}
        self.network.set_state_dict(params)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None \
                and os.path.exists(opt_path):
            self._optimizer.set_state_dict(fload(opt_path))
        return self

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        return summary(self.network, input_size, dtype)


def summary(net: Layer, input_size=None, dtype=None):
    """Layer-by-layer parameter summary (reference hapi/model_summary.py).
    Returns {'total_params': N, 'trainable_params': N} and prints a table.
    """
    rows = []
    total, trainable = 0, 0
    for name, sub in net.named_sublayers(include_self=True):
        own = [p for p in sub.parameters(include_sublayers=False)]
        if not own:
            continue
        n = sum(int(np.prod(p.shape)) for p in own)
        t = sum(int(np.prod(p.shape)) for p in own if not p.stop_gradient)
        rows.append((name or sub.__class__.__name__,
                     sub.__class__.__name__, n))
        total += n
        trainable += t
    width = max([len(r[0]) for r in rows], default=10) + 2
    print(f"{'Layer':<{width}}{'Type':<24}{'Params':>12}")
    print("-" * (width + 36))
    for name, typ, n in rows:
        print(f"{name:<{width}}{typ:<24}{n:>12,}")
    print("-" * (width + 36))
    print(f"Total params: {total:,}  Trainable params: {trainable:,}")
    return {"total_params": total, "trainable_params": trainable}
