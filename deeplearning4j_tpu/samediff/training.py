"""SameDiff training session.

Reference: org.nd4j.autodiff.samediff.internal.TrainingSession +
org.nd4j.autodiff.samediff.TrainingConfig (SURVEY.md §3.3). The reference
interprets the forward+backward graph op-by-op and applies updaters per
variable; here one jitted XLA program does forward, backward and the optax
update — full-graph HLO compile (BASELINE.json:10).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import register_config
from ..train.updaters import Adam, IUpdater, updater_from_any


@register_config
@dataclasses.dataclass(frozen=True)
class TrainingConfig:
    """Reference: TrainingConfig.Builder — updater + placeholder mappings."""

    updater: Optional[IUpdater] = None
    data_set_feature_mapping: tuple = ()
    data_set_label_mapping: tuple = ()
    l1: float = 0.0
    l2: float = 0.0
    minimize: bool = True


@dataclasses.dataclass
class History:
    """Reference: org.nd4j.autodiff.listeners.records.History."""

    loss_curve: List[float] = dataclasses.field(default_factory=list)


class TrainingSession:
    def __init__(self, sd, config: Optional[TrainingConfig],
                 listeners=None) -> None:
        from ..core.listeners import ListenerBus

        self.sd = sd
        self.config = config or TrainingConfig(updater=Adam(1e-3))
        self.updater = updater_from_any(self.config.updater or Adam(1e-3))
        self.tx = self.updater.to_optax()
        # trainable values keyed by node id
        self.var_ids = [
            n.id for n in sd._nodes.values() if n.kind == "variable"
        ]
        self.opt_state = None
        self._step = None
        # the most recent fit()'s History — still holds the flushed loss
        # curve when fit() is interrupted mid-run (robust telemetry)
        self.last_history: Optional[History] = None
        # TrainingListener bus (core/listeners.py): MetricsListener et al.
        # attach here. The per-step score is fetched from device ONLY when
        # some listener declares requires_score — otherwise listeners get
        # NaN and the loss stays on device (one stacked fetch per epoch).
        self.listeners = ListenerBus(listeners)
        self.iteration_count = 0
        self.epoch_count = 0
        self.last_batch_size: Optional[int] = None

    def _build_step(self):
        sd = self.sd
        cfg = self.config
        loss_name = sd._loss_name
        if loss_name is None:
            raise ValueError("SameDiff has no loss variable (set_loss_variables)")
        var_ids = self.var_ids

        def step(var_vals: Dict[int, Any], opt_state, feeds: Dict[str, Any], rng):
            def loss_of(vv):
                all_vals = dict(sd._values)
                all_vals.update(vv)
                out = sd._eval_graph(feeds, all_vals, [loss_name], rng=rng, training=True)
                loss = jnp.sum(out[loss_name])
                if cfg.l2:
                    for v in vv.values():
                        loss = loss + 0.5 * cfg.l2 * jnp.sum(jnp.square(v))
                if cfg.l1:
                    for v in vv.values():
                        loss = loss + cfg.l1 * jnp.sum(jnp.abs(v))
                return loss if cfg.minimize else -loss

            loss, grads = jax.value_and_grad(loss_of)(var_vals)
            updates, new_opt = self.tx.update(grads, opt_state, var_vals)
            import optax

            new_vals = optax.apply_updates(var_vals, updates)
            return new_vals, new_opt, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def fit(self, iterator, epochs: int = 1) -> History:
        sd = self.sd
        cfg = self.config
        var_vals = {i: sd._values[i] for i in self.var_ids}
        if self.opt_state is None:
            self.opt_state = self.tx.init(var_vals)
        if self._step is None:
            self._step = self._build_step()
        history = History()
        self.last_history = history
        from ..data.dataset import DataSet, MultiDataSet

        device_losses = []

        def flush_losses():
            if device_losses:
                # ONE stacked D2H fetch (iterating a jax array would fetch
                # per element — a device round-trip each)
                history.loss_curve.extend(
                    np.asarray(jnp.stack(device_losses), np.float64).tolist())
                device_losses.clear()

        bus = self.listeners
        use_listeners = bool(bus.listeners)
        need_score = use_listeners and bus.requires_score
        try:
            for _ in range(epochs):
                if use_listeners:
                    bus.epoch_start(self)
                for item in iterator:
                    if isinstance(item, MultiDataSet):
                        feats, labs = list(item.features), list(item.labels)
                    elif isinstance(item, DataSet):
                        feats, labs = [item.features], [item.labels]
                    else:
                        feats, labs = [item[0]], [item[1]]
                    feeds = {}
                    feeds.update(zip(cfg.data_set_feature_mapping, feats))
                    feeds.update(zip(cfg.data_set_label_mapping, labs))
                    feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
                    rng = sd._rng.next_key()
                    var_vals, self.opt_state, loss = self._step(var_vals, self.opt_state, feeds, rng)
                    # keep the loss ON DEVICE: a float() here would force a
                    # host sync per step and stop dispatch running ahead
                    # of the device. One stacked fetch per epoch costs one
                    # sync.
                    device_losses.append(loss)
                    if use_listeners:
                        self.iteration_count += 1
                        if feats:
                            shp = np.shape(feats[0])
                            self.last_batch_size = int(shp[0]) if shp else None
                        bus.iteration_done(
                            self, self.iteration_count, self.epoch_count,
                            float(loss) if need_score else float("nan"))
                flush_losses()
                if use_listeners:
                    bus.epoch_end(self)
                self.epoch_count += 1
        finally:
            # an exception / KeyboardInterrupt mid-epoch must not lose the
            # curve recorded so far — flush whatever is still on device
            flush_losses()
        sd._values.update(var_vals)
        return history
