"""The program under test, driven through its user entry points, one
module per model; ``configs/<config>.json`` names its module under
``model``. The harness gives a module the chips the cell runs on,
``devices``: the first ``chips`` of ``jax.devices()``, the same list
whose memory peak the run reports. Each module gives:

* ``rows(cfg)``: the rows a full-batch step reads (what ``feed.Feed``
  draws batches from);
* ``make_inputs(cfg, feed, seed, devices)``: the data, in the rows the
  feed gives each step, and the initial parameters, made by the
  benchmark from the seed, on ``devices``;
* ``Trainer(cfg, feed, inputs, spans, devices)``: the program's training
  step with its state, on ``devices``; ``step(i)`` dispatches step ``i``
  and returns what to wait for, ``loss(out)`` the loss in it,
  ``state()`` the state now;
* ``readings(cfg, feed, states, losses)``: ``check.readings`` of the
  program from its state before the first step, after the first and
  after the last of ``check.STEPS``;
* ``work(cfg, feed)``: what a step requires, counted from the shapes:
  ``flops``, the whole step's operations of the forward and backward
  passes over all its chips, and ``kernels``, per kernel the
  ``(flops, bytes)`` of each call that *each chip* makes through it.
  ``step.mfu`` divides the first by ``chips`` × one chip's peak; a
  kernel's roofline share expects ``steps × len(calls) × chips`` events
  of the kernel in the trace, and ``chips × roofline_s(calls)`` as the
  least time of their summed durations (``metrics_common``);
* ``FAULTS`` and ``fault(name)``: the faults the step can have, and a
  context manager that breaks the step while entered: at least
  ``"unchanged"`` (the state left as it was) and ``"half_batch"`` (half
  of each step's rows left out, the mean taken over the rest); a model
  on more than one chip adds ``"exchange"`` (the exchange between chips
  left out). A step built inside traces the broken code.

A configuration joins the benchmark as new files and appended entries
of ``BENCHMARK.json`` alone: ``configs/<config>.json``, this module and
``reference/<model>.py`` where its model is new, ``traffic/<mix>.json``
where its mix is new, ``cells/<cell>.json``, ``metrics/<metric>.py`` for
a new metric, and ``tiny/configs/<config>.json`` (and
``tiny/traffic/<mix>.json`` for a new mix): the keys to change so that
the CPU tests hold the cell, an empty object where none need change. A
cell on more than one chip runs in the CPU tests in a process of its
own, on as many virtual CPU devices.
"""

import jax


def one_chip(devices) -> None:
    """Raise unless ``devices`` is JAX's default device alone: a driver
    that places nothing itself runs there."""
    if list(devices) != jax.devices()[:1]:
        raise ValueError(f"this driver runs on one chip, JAX's default "
                         f"device; given {list(devices)}")
