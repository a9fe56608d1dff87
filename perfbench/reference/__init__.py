"""Plain JAX references, one module per model, importing nothing of the
program. ``run(cfg, inputs, dtype=jnp.float32, fault=None)`` follows
``check.STEPS`` training steps from the benchmark's initial parameters
on the same rows (``inputs``, made by ``models/<model>.make_inputs``)
and returns ``check.readings``. At float32
every product is taken at ``highest``. ``dtype=jnp.bfloat16`` is the
control: the data and every operation of the model in bfloat16, with
the parameters and the optimizer's state kept in float32 as a
mixed-precision step keeps them. ``fault="half_batch"``
leaves half of each step's rows out and takes the mean over the rest.
"""
