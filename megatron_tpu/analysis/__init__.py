"""Static analysis: tracing-discipline and communication auditing.

Two complementary layers (docs/static_analysis.md):

  * ``ast_lint`` — a stdlib-only AST linter with repo-specific rules
    (host syncs inside jitted code, banned APIs, jax._src
    imports, broad excepts, Python branching on traced arrays). The
    ``tools/jaxlint.py`` CLI loads it by file path so linting never
    pays a jax import.
  * ``jaxpr_audit`` + ``targets`` + ``contracts`` — trace the real
    jitted programs (train step, engine decode step, the
    pipeline/ring/ulysses/moe bodies) on CPU and audit their closed
    jaxprs: collectives per mesh axis with byte volumes, host
    callbacks, donation coverage, silent bf16->f32 promotions, rank-0
    scan carries inside shard_map bodies (the jax 0.4.37 miscompile),
    and sharding constraints on manually-bound axes. ``contracts``
    pins the collective counts/bytes of the key parallel configs to
    checked-in golden manifests (``analysis/golden/*.json``) asserted
    in tier-1 — the measurement seam ROADMAP item 2 builds on.

Submodules import lazily: ``ast_lint`` has no jax dependency, the
jaxpr layers pull jax only when used.
"""

__all__ = ["ast_lint", "jaxpr_audit", "targets", "contracts"]
