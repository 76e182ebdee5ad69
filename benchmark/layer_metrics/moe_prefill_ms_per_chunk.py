"""Own device time of the expert layers a prefill chunk: all seven
`moe_*` scopes (router, latent projections, dispatch, the held experts'
products, combine, the shared expert) inside the whole runs of
`jit_chunk_step`, where a chunk sends 512 x k rows through them. None
where none of the scopes occurs or no whole chunk step was traced."""

from benchmark.harness.trace import by_program

SCOPES = ("moe_router", "moe_latent_in", "moe_dispatch", "moe_experts",
          "moe_combine", "moe_latent_out", "moe_shared")


def read(run):
    found = [ms for ms in (by_program.scope_ms(run, "jit_chunk_step", scope)
                           for scope in SCOPES) if ms is not None]
    return sum(found) if found else None
