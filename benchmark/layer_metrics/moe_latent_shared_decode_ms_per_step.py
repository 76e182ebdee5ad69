"""Own device time of what every chip of a share computes whole for its
own rows in a decode step: the two latent projections around the routed
experts (`moe_latent_in`, `moe_latent_out`) and the shared expert
(`moe_shared`), inside the whole runs of `jit_decode_step`. None where
none of the three scopes occurs (an expert layer without them, a dense
model, a parent commit)."""

from benchmark.harness.trace import by_program

SCOPES = ("moe_latent_in", "moe_latent_out", "moe_shared")


def read(run):
    found = [ms for ms in (by_program.scope_ms(run, "jit_decode_step", scope)
                           for scope in SCOPES) if ms is not None]
    return sum(found) if found else None
