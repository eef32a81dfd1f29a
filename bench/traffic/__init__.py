"""Traffic mixes (``<mix>.json``) and the generator kinds that read them.

A mix file names a ``generator`` kind (a module here) and its ``params``.
Every generator kind exposes ``make(params, n_devices, seconds, seed)``
returning an object with:

* ``due(now_us) -> (dev, vals, sched_us)``: the events to post now, as
  device indices, float32 readings and int64 scheduled creation times in
  microseconds since the window opened;
* ``ts(sched_us, step) -> int array``: the event timestamps handed to
  ``post`` (strictly increasing per device);
* ``closed``: whether the next events wait for the engine (a closed loop
  posts only while less than one superstep's ingest waits on the host);
* ``tail_s``: seconds the mix keeps posting after the window closes, so
  the window's last events meet the same load as its first.

The generators are plain numpy, seeded only by ``--seed``, and import
nothing of the program.
"""
