"""The program under test, driven through its user entry points, one
module per model; ``configs/<config>.json`` names its module under
``model``. Each module gives:

* ``rows(cfg)``: the rows a full-batch step reads (what ``feed.Feed``
  draws batches from);
* ``make_inputs(cfg, feed, seed)``: the data, in the rows the feed
  gives each step, and the initial parameters, made by the benchmark
  from the seed, on the device;
* ``Trainer(cfg, feed, inputs, spans)``: the program's training step
  with its state; ``step(i)`` dispatches step ``i`` and returns what to
  wait for, ``loss(out)`` the loss in it, ``state()`` the state now;
* ``readings(cfg, feed, states, losses)``: ``check.readings`` of the
  program from its state before the first step, after the first and
  after the last of ``check.STEPS``;
* ``work(cfg, feed)``: what a step requires, counted from the shapes:
  ``flops`` of the forward and backward passes, and per kernel the
  ``(flops, bytes)`` of each call the step makes through it.
"""
