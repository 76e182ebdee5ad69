"""Runtime trace analysis: xplane profiler ingestion (ROADMAP item 2).

``jax.profiler`` (the train loop's ``--profile`` window, a SIGUSR1
window, the serving ``/admin/profile`` endpoint) writes ``*.xplane.pb``
protobufs — the XSpace/XPlane schema shared by XLA on every backend. This
package reads them with ZERO non-stdlib imports and turns the op events
into the runtime half of the comm measurement story the golden contracts
(``analysis/``) pin statically:

  * ``proto``   — minimal protobuf wire-format decoder (varint/fixed/
                  length-delimited), schema-free;
  * ``xplane``  — the XSpace schema walk: planes -> lines -> events with
                  interned stat/metadata strings resolved;
  * ``events``  — typed op events classified compute / collective /
                  transfer / host against ``analysis/taxonomy.py``;
  * ``analyze`` — per-step wall, top-K ops, per-collective total vs.
                  EXPOSED time (interval subtraction against concurrent
                  compute — the Flash Communication split, arXiv
                  2412.04964), and measured-vs-expected comparison
                  against the golden comm contracts.

``tools/trace_report.py`` is the CLI; it loads these modules by file
path so reading a trace never imports jax (docs/observability.md
"Runtime traces").
"""

from megatron_tpu.telemetry.tracing.analyze import (  # noqa: F401
    TraceReport, analyze_events, compare_contract,
)
from megatron_tpu.telemetry.tracing.events import (  # noqa: F401
    OpEvent, classify_xspace,
)
from megatron_tpu.telemetry.tracing.xplane import (  # noqa: F401
    XEvent, XLine, XPlane, XSpace, find_xplane_files, load_xspace,
)
