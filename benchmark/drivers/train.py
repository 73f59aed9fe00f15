"""Driver ``train``: the compiled step that
``ShardedTrainer.build_step_auto_layout`` returns, fed a batch from host
memory every step, for a window of seconds.

Set-up builds ONE step object with its state, drives it from the seed through
its first three steps (the feed and the call are the window's own), keeps what
the comparison needs from them on the host, and hands the same object to the
window.  The plain reference follows those three steps once the window has
closed and the program's state is gone.
"""
import time

import numpy as np

from benchmark.lib import compare

CHECKED_STEPS = 3


def half_doubled(batch):
    """The fault 'half of the batch left out, the mean taken over the rest':
    the first half's rows twice.  Never on the timed path: calibrate.py feeds
    it to the reference put in the program's place, and the tests plant it
    through a subclass that overrides ``next_batch``."""
    return {k: np.concatenate([v[:len(v) // 2]] * 2)
            for k, v in batch.items()}


def _leave_zero(_desc, _arr):
    """Initializer for ``init_state``: the benchmark places its own seeded
    weights, so the program's random init would be thrown away."""


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.traffic = ctx.cfg, ctx.traffic
        self.adapter = ctx.files.module("adapters", self.cfg["family"])
        self.ref = ctx.files.module("refs", self.cfg["family"])
        self.spans = ctx.spans

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax
        from mxnet_tpu.parallel.mesh import MeshSpec, make_mesh
        from mxnet_tpu.parallel.trainer import ShardedTrainer

        cfg, traffic, devices = self.cfg, self.traffic, self.ctx.devices
        tr = cfg["training"]
        spec = MeshSpec(make_mesh((len(devices),), ("dp",), devices=devices))
        trainer = ShardedTrainer(
            self.adapter.train_symbol(cfg), spec, lr=tr["lr"],
            momentum=tr["momentum"], wd=tr["wd"], zero=True,
            param_dtype=(None if tr["param_dtype"] == "float32"
                         else tr["param_dtype"]))
        shapes, in_dtypes = self.adapter.train_shapes(cfg, traffic)
        params, mom, aux = trainer.init_state(shapes, initializer=_leave_zero)
        self.names = list(trainer.param_names)
        self.aux_names = list(trainer.prog.aux_names)
        self.store_dtypes = {n: p.dtype for n, p in zip(self.names, params)}
        self.aux0 = tuple(np.asarray(a) for a in aux)
        weights = self.ref.make_weights(cfg, self.ctx.seed)
        params = tuple(
            jax.device_put(weights[n].astype(p.dtype),
                           trainer.param_sharding(n, p.shape))
            for n, p in zip(self.names, params))
        del weights
        self.p0 = {n: np.asarray(p) for n, p in zip(self.names, params)}
        self.step, self.params, self.mom, self.aux = \
            trainer.build_step_auto_layout(params, mom, aux, shapes,
                                           input_dtypes=in_dtypes or None)
        self.keys, self.bat = trainer._keys(), spec.batch_sharding()
        self.trainer = trainer
        self.guard = self.fresh_guard()
        self.plan_bytes = planned_bytes(self.step)
        self.first_steps()

    def fresh_guard(self):
        """The step's non-finite guard as a new trainer holds it: (loss
        scale, good streak).  The step donates the pair it is given."""
        import jax
        import jax.numpy as jnp
        rep = self.trainer.spec.replicated()
        return (jax.device_put(jnp.float32(self.trainer.init_loss_scale), rep),
                jax.device_put(jnp.int32(0), rep))

    def reseed(self, seed):
        """The same compiled step on another seed's weights and batches, the
        state placed in the layouts the step was compiled for (calibrate.py:
        one compile, a dozen seeds)."""
        import jax.numpy as jnp
        self.ctx.seed = int(seed)
        for_params, for_mom, for_aux = self.step.input_formats[0][:3]
        weights = self.ref.make_weights(self.cfg, seed)
        self.p0 = {n: np.asarray(weights[n].astype(self.store_dtypes[n]))
                   for n in self.names}
        del weights
        self.params = tuple(relaid(self.p0[n], f)
                            for n, f in zip(self.names, for_params))
        self.mom = tuple(
            relaid(jnp.zeros(self.p0[n].shape, jnp.float32), f)
            for n, f in zip(self.names, for_mom))
        self.aux = tuple(relaid(a, f) for a, f in zip(self.aux0, for_aux))
        self.guard = self.fresh_guard()
        self.first_steps()

    def first_steps(self):
        """The steps that are compared later, on this seed's batches: the
        momentum after the first and the parameters after the last go to the
        host."""
        self.batches = self.adapter.train_batches(self.cfg, self.traffic,
                                                  self.ctx.seed)
        if len(self.batches) < CHECKED_STEPS:
            raise ValueError("the traffic needs %d rotating batches or more"
                             % CHECKED_STEPS)
        self.n_fed = 0
        self.first_loss = []
        for i in range(CHECKED_STEPS):
            loss, _ok = self.feed()
            self.first_loss.append(float(loss))
            if i == 0:
                self.m1 = {n: np.asarray(m)
                           for n, m in zip(self.names, self.mom)}
        self.p3 = {n: np.asarray(p) for n, p in zip(self.names, self.params)}

    def feed(self):
        """One step: the next batch of the rotating set goes from host memory
        to the device, and the compiled step is called on it."""
        import jax
        batch = self.next_batch()
        with self.spans.span("upload"):
            inputs = {k: jax.device_put(v, self.bat)
                      for k, v in batch.items()}
        with self.spans.span("enqueue"):
            out = self.call_step(inputs)
        self.params, self.mom, self.aux, loss, ok, self.guard = out
        return loss, ok

    def next_batch(self):
        batch = self.batches[self.n_fed % len(self.batches)]
        self.n_fed += 1
        return batch

    def call_step(self, inputs):
        return self.step(self.params, self.mom, self.aux, inputs, self.keys,
                         self.guard)

    # -- the window --------------------------------------------------------
    def window(self, seconds):
        """Steps for ``seconds``, one step enqueued ahead of the one waited
        for, closed by ``block_until_ready`` on the last step's outputs."""
        import jax
        self.spans.reset()
        oks = []
        t0 = time.perf_counter()
        prev = self.feed()
        while True:
            cur = self.feed()
            jax.block_until_ready(prev[0])
            oks.append(prev[1])
            prev = cur
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready((prev[0], self.params))
        window_s = time.perf_counter() - t0
        oks.append(prev[1])
        steps = len(oks)
        failed = sum(1 for ok in oks if not bool(ok))
        return {"window_s": window_s, "steps": steps, "attempted": steps,
                "failed": failed,
                "work_per_step": self.adapter.work_per_step(self.cfg,
                                                            self.traffic),
                "host_seconds": dict(self.spans.seconds)}

    def traced_segment(self, seconds):
        """The same loop, straight after the window, under the profiler."""
        out = self.window(seconds)
        return {k: out[k] for k in ("window_s", "steps", "host_seconds")}

    def release(self, keep_step=False):
        """Frees the program's state (and its step, unless another seed is
        to follow) before the reference runs."""
        for name in ("params", "mom", "aux", "guard") + (
                () if keep_step else ("step", "keys", "trainer")):
            setattr(self, name, None)

    # -- the comparison ----------------------------------------------------
    def program_readings(self):
        tr = self.cfg["training"]
        f32 = lambda x: np.asarray(x, np.float32)
        grads = {n: -f32(self.m1[n]) / tr["lr"] - tr["wd"] * f32(self.p0[n])
                 for n in self.names}
        change = {n: f32(self.p3[n]) - f32(self.p0[n]) for n in self.names}
        return {"loss": list(self.first_loss),
                "grad_norms": compare.leaf_norms(grads),
                "change_norms": compare.leaf_norms(change)}

    def reference_readings(self, alter=None, **how):
        """The reference over the compared steps; with ``cast`` the control
        (its matrix products' operands rounded to that type), with ``alter``
        a fault planted in its batches, with ``nudge`` its first parameters
        moved by that share (calibrate.py)."""
        batches = self.batches[:CHECKED_STEPS]
        if alter is not None:
            batches = [alter(b) for b in batches]
        return self.ref.train_reference(
            self.cfg, self.ctx.seed, self.store_dtypes, batches,
            self.traffic, **how)

    def verify(self):
        return checks(self.program_readings(), self.reference_readings(),
                      self.ctx.limits)


def relaid(x, fmt):
    """``x`` on the device in the layout the compiled step asks for: placed
    first, then re-laid on the device as the trainer itself does it (a host
    array put straight into a non-default layout came back in another one on
    the v5e, and the step then refuses its own state)."""
    import jax
    y = jax.device_put(jax.device_put(x, fmt.sharding), fmt)
    if y.format.layout != fmt.layout:
        raise RuntimeError("re-laying %s%s gave layout %s, not %s"
                           % (x.dtype, x.shape, y.format.layout, fmt.layout))
    return y


def planned_bytes(step):
    """What the compiler planned for the timed program (arguments, outputs
    and temporaries less what they alias), or None where the step object
    does not say."""
    try:
        m = step.memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)
    except Exception:
        return None


# the numbers a cell's limits file may hold: (readings compared, statistic)
NUMBERS = {"grad_norm_gap": ("grad_norms", "worst"),
           "grad_norm_gap_median": ("grad_norms", "median"),
           "change_norm_gap": ("change_norms", "worst"),
           "change_norm_gap_median": ("change_norms", "median")}


def checks(prog, ref, limits):
    """[(name, value, limit, where)] for each number the cell's limits name:
    the gap of norms (``compare.leaf_gaps``) of the first gradient or of the
    parameters' change, by the worst leaf or by the median leaf.  The step's
    own 'loss' output is the sum of the head's softmax outputs (the row
    count, whatever the weights): neither the control nor a fault moves it,
    so it has no upper reading and is not compared; a non-finite one trips
    the step's guard and counts under ``failed``."""
    still = compare.still_leaves(ref["grad_norms"])
    out = []
    for name, limit in limits.items():
        key, statistic = NUMBERS[name]
        gaps = compare.leaf_gaps(prog[key], ref[key],
                                 skip=still if key == "change_norms" else ())
        if not all(np.isfinite(v) for v in gaps.values()):
            out.append((name, float("inf"), limit, "a non-finite norm"))
        elif statistic == "worst":
            leaf = max(gaps, key=gaps.get)
            out.append((name, gaps[leaf], limit, leaf))
        else:
            out.append((name, float(np.median(list(gaps.values()))), limit,
                        None))
    return out


def loss_gap(prog, ref):
    """Not compared (see ``checks``); calibrate.py prints it."""
    return max(abs(a - b) / max(abs(b), 1e-300)
               for a, b in zip(prog["loss"], ref["loss"]))
