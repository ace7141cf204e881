"""Traffic kind ``ondevice_epochs``: a dataset that lives on the device,
trained an epoch a call through ``fit_on_device(x, y, batch_size=...,
epochs=1)``, again and again until the time is up.  One call is one
dispatch of the program's epoch program, which gathers each minibatch from
the dataset itself.

The cell's file gives ``batches`` (minibatches in the dataset); the
configuration's file the sizes, ``batch_size`` and the ``precision``.

The entry shows the state after a whole epoch and that epoch's last loss,
nothing in between.  So the first steps the reference follows are driven
through the same call on the same images with labels on the last
``check_steps`` batches only: a batch without labels has no loss and no
gradient, and before the first labelled batch nothing moves.  After that
epoch the loss is the last step's, the momentum's trace holds the gradients
of those steps as the optimizer gathered them, and the weights have moved by
those steps alone.  It runs in the dataset's own order (``shuffle=False``),
so that the reference knows the batches; the window shuffles, as the default
does, and one more epoch on the full labels warms that path.  All of them
are the same compiled program.
"""
from __future__ import annotations

import gc
import math
import time

from benchmark import common, program
from benchmark.check import train as check_train


def make_dataset(key, n: int, batch: int, image: int, channels: int,
                 classes: int):
    """``n`` batches of standard-normal images in bfloat16 and one-hot
    float32 labels, made on the device in one jitted call, a batch at a
    time so that only the result is ever whole."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.lax.map(
            lambda k: jax.random.normal(k, (batch, image, image, channels),
                                        jnp.bfloat16),
            jax.random.split(kx, n))
        ids = jax.random.randint(ky, (n * batch,), 0, classes)
        return (x.reshape(n * batch, image, image, channels),
                jax.nn.one_hot(ids, classes, dtype=jnp.float32))
    return make(key)


class Job:
    def __init__(self, cell: dict, cfg: dict, seed: int, devices):
        self.cell, self.cfg, self.seed, self.devices = cell, cfg, seed, devices
        self.net = None
        self.x = self.y = None
        self.program = None          # its readings of the first steps
        self.batch = cfg["batch_size"]
        self.reference_module = common.load_module("reference", cfg["family"])

    # ------------------------------------------------------------- set-up
    def build(self):
        from deeplearning4j_tpu import models
        cfg = self.cfg
        builder = getattr(models, cfg["program_model"])
        compute = None if cfg["precision"] == "float32" else cfg["precision"]
        size = cfg["image_size"]
        return builder(num_classes=cfg["num_classes"], compute_dtype=compute,
                       input_shape=(size, size, cfg["image_channels"]),
                       updater=program.updater(cfg)).init()

    def dataset(self):
        cfg = self.cfg
        if self.x is None:
            self.x, self.y = make_dataset(
                common.seed_key(self.seed, 1), self.cell["batches"],
                self.batch, cfg["image_size"], cfg["image_channels"],
                cfg["num_classes"])

    def _weights(self):
        return self.reference_module.init_params(
            self.cfg, common.seed_key(self.seed))

    def _epoch(self, y, shuffle):
        import jax
        with jax.profiler.TraceAnnotation("bench.fit"):
            self.net.fit_on_device(self.x, y, batch_size=self.batch,
                                   epochs=1, shuffle=shuffle)
        return float(self.net.get_score())

    def setup(self):
        import jax
        import jax.numpy as jnp
        cell, cfg = self.cell, self.cfg
        n = cell["batches"]
        t0 = time.perf_counter()
        # the dataset first: nothing else is on the device yet
        self.dataset()
        jax.block_until_ready(self.x)
        t_data = time.perf_counter()
        self.net = net = self.build()
        weights = self._weights()
        missing = set(k for k, v in net.params.items() if v) ^ set(weights)
        if missing:
            raise RuntimeError(f"the program's layers and the reference's "
                               f"differ: {sorted(missing)[:6]}")
        net.params = {**{k: v for k, v in net.params.items() if not v},
                      **weights}
        jax.block_until_ready(net.params)
        t_weights = time.perf_counter()

        first = (n - cell["check_steps"]) * self.batch
        labelled = jnp.where(jnp.arange(n * self.batch)[:, None] >= first,
                             self.y, 0.0)
        loss = self._epoch(labelled, False)
        del labelled
        t_first = time.perf_counter()
        dot = lambda d: {f"{k}.{kk}": v for (k, kk), v in d.items()}
        self.program = {
            "losses": [loss],
            "grad_norms": dot(program.leaf_norms(
                program.optimizer_field(net.opt_state, "trace"))),
            "delta_norms": dot(program.leaf_norms(
                {k: v for k, v in net.params.items() if v},
                minus=self._weights()))}
        # the window's own call, shuffled as the default is
        self._epoch(self.y, True)
        jax.block_until_ready(net.params)
        common.say(
            f"ondevice_epochs: {n} batches of {self.batch} on the device in "
            f"{t_data - t0:.1f} s ({self.x.nbytes / 1e9:.2f} GB of images); "
            f"model and weights {t_weights - t_data:.1f} s; first epoch "
            f"(compile or cache load) {t_first - t_weights:.1f} s, one more "
            f"{time.perf_counter() - t_first:.1f} s; loss after "
            f"{cell['check_steps']} steps {loss:.4f}")

    # ------------------------------------------------------------- windows
    def _run(self, seconds, t_start=None):
        import jax
        net = self.net
        before, failed = net.iteration, 0
        t0 = time.perf_counter()
        while True:
            score = self._epoch(self.y, True)
            if not math.isfinite(score):
                failed += 1
            if seconds is None or time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(net.params)
        took = time.perf_counter() - t0
        steps = net.iteration - before
        common.say(f"ondevice_epochs: {steps} steps in {took:.3f} s, "
                   f"{steps * self.batch / took:.1f} examples/s, last loss "
                   f"{score:.4f}")
        metrics = {"train_step_ms": 1e3 * took / max(steps, 1)}
        if t_start is not None:
            metrics["setup_s"] = t0 - t_start
        return {"steps": steps, "attempted": steps, "failed": failed,
                "metrics": metrics}

    def window(self, seconds: float, t_start: float):
        return self._run(seconds, t_start)

    def traced_stretch(self):
        return self._run(None)

    # --------------------------------------------------------------- after
    def release(self):
        program.free(self.net)
        self.net = None
        self.x = self.y = None
        gc.collect()

    def checked_batches(self):
        """The labelled batches of the first epoch, copied out of the
        dataset."""
        import jax.numpy as jnp
        self.dataset()
        n, b = self.cell["batches"], self.batch
        return [(jnp.array(self.x[i * b:(i + 1) * b]),
                 jnp.array(self.y[i * b:(i + 1) * b]))
                for i in range(n - self.cell["check_steps"], n)]

    def reference(self, batches, precision="float32", keep_rows=None):
        """The reference's readings, as the entry shows the program's: the
        last step's loss, and the trace where the first gradient would
        be."""
        ref = self.reference_module.train_steps(
            self.cfg, common.seed_key(self.seed), batches, precision,
            keep_rows)
        return {"losses": ref["losses"][-1:],
                "grad_norms": ref["trace_norms"],
                "delta_norms": ref["delta_norms"]}

    def check(self):
        """Run once the window has closed; frees the program's state and
        the dataset but for the compared batches."""
        batches = self.checked_batches()
        self.release()
        read = check_train.readings(self.program, self.reference(batches))
        common.say(f"ondevice_epochs: worst leaves {read['_where']}")
        return check_train.verdict(read, self.cell.get("limits", {}))

    def flops_per_step(self, flops_module):
        return flops_module.train_step_flops(self.cfg, self.batch)
