"""Seconds inside ``hvd.init()``, by the program's own start-up marks
(``horovod_tpu.utils.spans.marks()``): from ``hvd.init`` (the call is
entered) to ``hvd.init.plane`` (the ``xla_ici`` device plane is up:
the native core loaded and its controller met, ``jax.distributed`` where
there are ranks to meet, the backend client, the registration with the
core). ``None`` for a program without the marks or a lane that never
brings the plane up."""

from chipbench.layer_metrics import setup_first_step_s


def read(ctx):
    entered, up = (setup_first_step_s.mark(name)
                   for name in ("init", "init.plane"))
    if entered is None or up is None:
        return None
    return up - entered
