"""Own device time of what stands around the experts' products in a
decode step: the router (its product over the router's whole width, the
scores, the choice and the rows' counts: `moe_router`), the sort and the
gather into expert order (`moe_dispatch`) and the gather back with the
gates' weighted sum (`moe_combine`), inside the whole runs of
`jit_decode_step`. None where none of the three scopes occurs."""

from benchmark.harness.trace import by_program

SCOPES = ("moe_router", "moe_dispatch", "moe_combine")


def read(run):
    found = [ms for ms in (by_program.scope_ms(run, "jit_decode_step", scope)
                           for scope in SCOPES) if ms is not None]
    return sum(found) if found else None
