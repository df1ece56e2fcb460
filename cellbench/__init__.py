"""Cell benchmark of the compression framework on one TPU.

One command runs one cell (a configuration under a traffic mix) once::

    python -m cellbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the checkout lists the cells.  Each
configuration, traffic mix, per-layer metric and correctness limit is a file
of its own, found by the name the cell gives it:

* ``configs/<config>.json``: the deployment (source, sizes, guarantees);
* ``traffic/<mix>.json``: the mix's parameters and the traffic driver that
  runs it (``drivers/<driver>.py``);
* ``layer_metrics/<metric>.py``: one reader per per-layer metric;
* ``limits/<cell>.json``: the limits that decide ``correct``;
* ``peaks.json``: published peaks by ``device_kind``.

The yardstick lives here and not in the program: the field generator
(``solver.py``), the region-key generator (``traffic.py``), the comparison
with the reference (``check.py``), the reduction of profiler traces
(``devtrace.py``) and the kernels' operation and byte counts
(``roofline.py``).
"""
