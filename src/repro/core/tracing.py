"""Program spans: what the streaming executor and the tiled writer were doing,
on the profiler's clock.

``span(name, **stats)`` is ``jax.profiler.TraceAnnotation("repro.<name>",
**stats)``.  With no profiler session active it costs about a microsecond
and records nothing; under ``jax.profiler.trace`` each span becomes an event
named ``repro.<name>`` on its thread's line of the ``/host:CPU`` plane, with
its stats, on the same clock as the device's operations.  There is no flag,
buffer or exporter of its own: the profiler's trace is the record.  Stats
are integers; a stat known only at the end of the work is added with the
span's ``set_metadata(bytes=n)``.  ``row0``/``col0`` are the origin of the
output region the span serves, so one strip's spans on different threads
share them; ``bytes`` is what that span moved.

The spans, by thread (this list is the contract the benchmark's readers and
operators rely on):

A thread that dispatches the device programs: the one that calls
``StreamingExecutor.run``, or each worker of ``run_pool``; both run the
same region loop.  Its spans do not overlap and cover its pass but for a
little glue between them, so each idle second of the chip lies in one of
them or in that small remainder.  ``begin`` and ``end`` frame the pass
once, on the calling thread:

- ``begin``: ``mapper.begin`` before the first strip (a file sink truncates
  and lays out its file);
- ``wait_inputs`` (``row0``, ``col0``): waiting for a strip's prefetched
  inputs (the describe pass, plan lookup and source read), or, at
  read-ahead depth 0 (``prefetch=0``, and every ``run_pool`` worker),
  doing that work inline;
- ``dispatch`` (``row0``, ``col0``): enqueueing the strip's program and
  waiting until the device has run it; the device is busy for most of it,
  and what idles there is the program's launch and completion latency;
- ``d2h`` (``row0``, ``col0``, ``bytes``): pulling a finished strip's
  output from the device to the host, after the device has finished it;
  ``bytes`` are the strip's pixel bytes (not the lane padding its rows
  travel with);
- ``wait_write`` (``row0``, ``col0``): handing a strip to the write stage,
  blocked while the write-behind queue is full, or, at depth 0, the inline
  ``mapper.consume``; without an origin, the drain of the write-behind
  queue at the end of the pass;
- ``end``: ``mapper.end`` after the last strip (a file sink writes its
  index and seals its header).

The prefetch threads (at depth 0, the dispatching thread, inside
``wait_inputs``):

- ``describe`` (``row0``, ``col0``): the describe pass of a strip;
- ``read`` (``row0``, ``col0``, ``bytes``, ``inputs``): the strip's source
  reads (``read_sources``), one array per input at its described window;
  ``bytes`` are the arrays' bytes, ``inputs`` how many arrays there are.
  Rows an input's windows share across strip seams count in each strip.

The thread that runs ``TileWriter.consume`` (the write-behind thread, or
the dispatching thread at depth 0) and the one that runs its ``end``:

- ``consume`` (``row0``, ``col0``, ``bytes``, ``ranges``, ``interleaved``):
  one region written straight to its tiles' byte ranges at every pyramid
  level; ``bytes`` are the pixel bytes written, ``ranges`` the byte ranges,
  one per ``pwrite``/``pwritev`` call, ``interleaved`` the level-0 bytes
  the writer had to interleave on the host first (0 when the region's rows
  came C-ordered in the file's dtype);
- ``flush`` (``bytes``): ``TileWriter.end``; ``bytes`` are the index and
  the header it writes.

Over one pass, the ``consume`` and ``flush`` bytes add up to the size of
the finished file, the ``d2h`` bytes to the output's bytes, and the
``read`` bytes to the described windows' bytes over every strip.
"""
from __future__ import annotations

import jax


def span(name: str, **stats: int) -> jax.profiler.TraceAnnotation:
    """A profiler span named ``repro.<name>`` carrying integer ``stats``."""
    return jax.profiler.TraceAnnotation(f"repro.{name}", **stats)
