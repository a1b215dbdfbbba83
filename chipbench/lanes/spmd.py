"""Lane "spmd": the jitted SPMD layer's main path on one chip —
``parallel.make_split_train_step`` (a grad program and a donated apply
program), set up as ``bench.run_spmd`` sets it up."""

import jax


class Lane:
    def __init__(self, traffic):
        if traffic["ranks"] != 1:
            raise SystemExit("lane spmd: one process on one chip; a "
                             "mesh over chips is a lane of its own")
        self.rank, self.size = 0, 1

    def start(self):
        """Before the backend is touched: nothing to bring up."""

    def build(self, model, key, jit_kwargs):
        """-> (step, carry, lowered text of the grad program)."""
        from horovod_tpu.parallel import make_split_train_step

        if model.has_state:
            raise SystemExit("lane spmd: make_split_train_step takes "
                             "loss_fn(params, batch); this model "
                             "carries state")

        def loss_fn(params, batch):
            return model.loss(params, (), batch)[0]

        ts = make_split_train_step(loss_fn, model.optimizer(1),
                                   jit_kwargs=jit_kwargs)
        k_init, k_batch = jax.random.split(key)
        params, _ = jax.jit(model.init)(k_init)
        batch = jax.jit(model.batch)(k_batch)
        # The SAME expression the split step jits, so the lowering read
        # here is the program that runs.
        text = jax.jit(lambda p, d: jax.value_and_grad(loss_fn)(p, d),
                       **jit_kwargs).lower(params, batch).as_text()

        def step(carry, batch):
            with jax.profiler.TraceAnnotation("enqueue_step"):
                return ts.step(carry, batch)

        return step, ts.init(params), batch, text

    def params_of(self, carry):
        return carry[0]

    def agree(self, n):
        return n

    def counters(self):
        return {}

    def check(self, counters, steps, grad_bytes, params):
        return []

    def close(self):
        pass
