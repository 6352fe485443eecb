"""How a traffic mix's requests reach the server, one module per way.

A mix file names its loop (``"loop": "closed_batch"``), and
:func:`bench.harness.traffic.loop` imports ``bench/loops/<loop>.py`` by that
name. A loop module provides:

- ``generate(mix, seed, vocab_size) -> List[Request]``: the mix's requests,
  sizes from the mix, token ids from the run seed;
- ``server_sizes(mix, requests, page_size) -> dict``: ``max_batch``,
  ``cache_len`` and ``num_pages`` of the engine that serves them;
- ``Drive(scheduler, requests, on_token)``, with
  ``setup(seconds, tick_floor_s, log)`` (everything before the window:
  submissions, prefill, warming each program the window can reach),
  ``before_tick()`` (what the loop does before each window tick, such as
  submitting what is due), ``contexts()`` (the context each sequence of the
  next tick attends), ``due()`` (the requests the window must serve) and
  ``served()`` (each request's prompt and served tokens, for the check).

The harness keeps only the clock, the window, the trace and the check; a new
way of arriving is a new module here, and a new mix a new data file.
"""
