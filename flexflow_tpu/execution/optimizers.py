"""Optimizers: SGD (momentum/nesterov) and Adam.

Reference: src/runtime/optimizer.cc (608 LoC) + optimizer_kernel.cu — per-weight
update tasks in two sync modes (parameter-server and NCCL allreduce,
optimizer_kernel.cu:88,196). TPU-native: a pure ``(params, grads, state) ->
(params, state)`` pytree transform; gradient synchronization disappears into
sharded autodiff (psum on the data axis), so both reference sync modes collapse
into the same code path. The FlexFlow class surface (SGDOptimizer/AdamOptimizer
with ``next()`` per-step hyperparameter schedule, optimizer.h:27-96) is kept.
"""
from __future__ import annotations


def _step_counter(params):
    """The step counter of a fresh state, with the signature the train step
    hands it back with: an int32 array that is not weakly typed, committed
    and replicated on the mesh the parameters live on. A bare ``0`` here
    would make a process's first call of the jitted step one program and
    every later call another (ROADMAP.md S12). Made on the host and put:
    no program is built for it."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    leaves = jax.tree_util.tree_leaves(params)
    placed = getattr(leaves[0], "sharding", None) if leaves else None
    # off a mesh: uncommitted, as a jit over uncommitted parameters returns it
    replicated = (NamedSharding(placed.mesh, PartitionSpec())
                  if isinstance(placed, NamedSharding) else None)
    return jax.device_put(np.zeros((), np.int32), replicated)


class Optimizer:
    def init_state(self, params):
        """A fresh state whose every leaf enters the train step as the step
        returns it: the moments are ``zeros_like`` their parameter (its
        sharding and committedness), the counter is ``_step_counter``."""
        raise NotImplementedError

    def next(self, state):
        """Per-step hyperparameter advance (reference: AdamOptimizer::next,
        optimizer.cc — updates alpha_t); returns new state."""
        return state

    def update(self, params, grads, state):
        raise NotImplementedError

    def set_learning_rate(self, lr: float) -> None:
        """reference: optimizer.h set_learning_rate (used by the Keras
        LearningRateScheduler callback). The jitted train step bakes the
        rate in as a constant, so callers must rebuild it — the keras fit
        loop watches ``_lr_changed``."""
        if hasattr(self, "lr"):
            self.lr = float(lr)
        else:
            self.alpha = float(lr)
        self._lr_changed = True


class SGDOptimizer(Optimizer):
    """reference: optimizer.h:36-60 (lr, momentum, nesterov, weight_decay)."""

    def __init__(self, ffmodel=None, lr: float = 0.01, momentum: float = 0.0,
                 nesterov: bool = False, weight_decay: float = 0.0):
        self.lr = lr
        self.momentum = momentum
        self.nesterov = nesterov
        self.weight_decay = weight_decay

    def init_state(self, params):
        import jax
        import jax.numpy as jnp

        state = {"step": _step_counter(params)}
        if self.momentum != 0.0:
            state["velocity"] = jax.tree_util.tree_map(jnp.zeros_like, params)
        return state

    def update(self, params, grads, state):
        import jax

        lr, mom, wd = self.lr, self.momentum, self.weight_decay

        if mom == 0.0:
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - lr * (g + wd * p), params, grads)
            return new_params, {"step": state["step"] + 1}

        def upd(p, g, v):
            g = g + wd * p
            v_new = mom * v + g
            step = (g + mom * v_new) if self.nesterov else v_new
            return p - lr * step, v_new

        flat = jax.tree_util.tree_map(upd, params, grads, state["velocity"])
        new_params = jax.tree_util.tree_map(lambda t: t[0], flat,
                                            is_leaf=lambda t: isinstance(t, tuple))
        new_vel = jax.tree_util.tree_map(lambda t: t[1], flat,
                                         is_leaf=lambda t: isinstance(t, tuple))
        return new_params, {"step": state["step"] + 1, "velocity": new_vel}


class AdamOptimizer(Optimizer):
    """reference: optimizer.h:77-96 (alpha, beta1, beta2, weight_decay,
    epsilon; alpha_t bias-corrected schedule via ``next()``, optimizer.cc).

    ``moment_dtype``: TPU-native extension beyond the reference — store the
    m/v moments in a reduced dtype (e.g. ``jnp.bfloat16``). The update math
    stays f32 (moments are upcast, the fresh values rounded once at store),
    but the optimizer's HBM traffic drops from ~28 to ~16 bytes/param —
    Adam is fused into the weight-gradient fusions of a BERT-Large step
    (PERF.md §5), so what the knob buys there is not separable, and no cell
    of the benchmark sets it. None (default) keeps exact reference numerics,
    and every cell of the benchmark trains with None."""

    def __init__(self, ffmodel=None, alpha: float = 0.001, beta1: float = 0.9,
                 beta2: float = 0.999, weight_decay: float = 0.0,
                 epsilon: float = 1e-8, moment_dtype=None):
        self.alpha = alpha
        self.beta1 = beta1
        self.beta2 = beta2
        self.weight_decay = weight_decay
        self.epsilon = epsilon
        self.moment_dtype = moment_dtype

    def init_state(self, params):
        import jax
        import jax.numpy as jnp

        dt = self.moment_dtype

        def zeros(p):
            return jnp.zeros_like(p, dtype=dt) if dt is not None \
                else jnp.zeros_like(p)

        return {"step": _step_counter(params),
                "m": jax.tree_util.tree_map(zeros, params),
                "v": jax.tree_util.tree_map(zeros, params)}

    def update(self, params, grads, state):
        import jax
        import jax.numpy as jnp

        step = state["step"] + 1
        b1, b2, eps, wd = self.beta1, self.beta2, self.epsilon, self.weight_decay
        # bias-corrected alpha_t exactly as the reference's next() computes it
        alpha_t = self.alpha * jnp.sqrt(1.0 - b2 ** step) / (1.0 - b1 ** step)
        dt = self.moment_dtype

        def upd(p, g, m, v):
            g = g + wd * p
            if dt is not None:  # f32 math over reduced-precision storage
                # explicitly f32, NOT p.dtype: with bf16 params the (1-b2)
                # g^2 contributions would fall below bf16's mantissa and v
                # would stop accumulating
                m = m.astype(jnp.float32)
                v = v.astype(jnp.float32)
            m_new = b1 * m + (1 - b1) * g
            v_new = b2 * v + (1 - b2) * jnp.square(g)
            p_new = p - alpha_t * m_new / (jnp.sqrt(v_new) + eps)
            if dt is not None:
                m_new = m_new.astype(dt)
                v_new = v_new.astype(dt)
            return p_new, m_new, v_new

        trip = jax.tree_util.tree_map(upd, params, grads, state["m"], state["v"])
        is_leaf = lambda t: isinstance(t, tuple)
        new_params = jax.tree_util.tree_map(lambda t: t[0], trip, is_leaf=is_leaf)
        new_m = jax.tree_util.tree_map(lambda t: t[1], trip, is_leaf=is_leaf)
        new_v = jax.tree_util.tree_map(lambda t: t[2], trip, is_leaf=is_leaf)
        return new_params, {"step": step, "m": new_m, "v": new_v}
