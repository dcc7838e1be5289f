"""``compiles_in_window`` (``step_loop`` layer, count): programs JAX built or
loaded inside the measured window — its own
``/jax/core/compile/backend_compile_duration`` events, which cover the
trainer's ObservedJit steps and every eager op alike.  Must be 0: a new
shape inside the window is a compile inside the window."""


def read(run):
    return run.compiles_in_window()
