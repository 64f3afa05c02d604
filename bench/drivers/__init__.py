"""Window drivers, one file each (``bench/drivers/<driver>.py``), found
by the traffic file's ``driver``.

A driver module has one function, ``prepare(cell, graph, workdir)``,
which writes the cell's inputs and returns an object with

- ``units``: the work of one operation, in the unit the end-to-end
  metrics count (edges for a load);
- ``input_bytes``: the bytes one operation reads, for the rooflines;
- ``op(k, mark)``: the timed path, operation ``k`` of the run, which
  returns once its result is ready.  ``mark(name)`` is a context
  manager the driver brackets its phases with: a profiler annotation in
  a traced run, nothing otherwise;
- ``check(results)``: ``(checks, failed)`` after the window, each number
  compared beside its limit and the operations that broke one.
"""
