"""The jax API surface the code relies on, pinned as statements about the
installed jax (0.9): axis_size inside shard_map, partial-manual
shard_map semantics, the ambient-mesh accessors — plus the jaxlint
banned-API rules (megatron_tpu/analysis/ast_lint.py) that encode what
the code must not reach for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from megatron_tpu.analysis import ast_lint
from megatron_tpu.config import ParallelConfig
from megatron_tpu.parallel.mesh import ambient_mesh_shape, build_mesh


def _mesh(cp=2):
    return build_mesh(ParallelConfig(context_parallel=cp)).mesh


def test_axis_size_inside_shard_map():
    mesh = _mesh(cp=2)
    got = {}

    def body(x):
        got["one"] = jax.lax.axis_size("context")
        return x

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("context"),),
                      out_specs=P("context"), check_vma=False)
    fn(jnp.zeros((4, 4)))
    assert got["one"] == 2


def test_axis_size_tuple_and_unbound():
    """Tuple axes multiply; an unbound name raises NameError."""
    mesh = _mesh(cp=2)
    got = {}

    def body(x):
        got["pair"] = jax.lax.axis_size(("data", "context"))
        with pytest.raises(NameError):
            jax.lax.axis_size("no-such-axis")
        return x

    fn = jax.shard_map(body, mesh=mesh,
                      in_specs=(P(("data", "context")),),
                      out_specs=P(("data", "context")), check_vma=False)
    fn(jnp.zeros((8, 4)))
    # no axis_names = manual over the whole mesh: data=4 x context=2
    assert got["pair"] == 8


def test_abstract_mesh_is_empty_without_a_mesh():
    """With no mesh set the accessor yields None or an EMPTY mesh — the
    `mesh is None or not mesh.shape` guards cover both."""
    m = jax.sharding.get_abstract_mesh()
    assert m is None or hasattr(m, "shape")
    assert ambient_mesh_shape() == {}


def test_set_mesh_publishes_to_all_accessors():
    mesh = _mesh(cp=2)
    with jax.sharding.set_mesh(mesh):
        am = jax.sharding.get_abstract_mesh()
        assert am is not None and dict(am.shape)["context"] == 2
        assert ambient_mesh_shape()["context"] == 2
        # legacy thread_resources path: bare-PartitionSpec constraints
        # inside jit must resolve against the ambient mesh
        out = jax.jit(lambda x: jax.lax.with_sharding_constraint(
            x, P("context")))(jnp.zeros((4, 4)))
        assert out.shape == (4, 4)
    assert ambient_mesh_shape() == {}


# ---------------------------------------------------------------------------
# linter rules
# ---------------------------------------------------------------------------


def test_linter_bans_what_the_toolchain_lacks():
    """ragged_all_to_all / legacy partial-auto shard_map / direct
    experimental imports are linter-banned."""
    snippet = (
        "import jax\n"
        "from jax.experimental.shard_map import shard_map\n"
        "def f(x):\n"
        "    y = jax.lax.ragged_all_to_all(x, x, x, x, x, x,"
        " axis_name='ep')\n"
        "    return jax.shard_map(lambda a: a, mesh=None, in_specs=(),"
        " out_specs=(), auto=frozenset({'data'}))\n"
    )
    findings = ast_lint.lint_source(snippet, "snippet.py")
    msgs = "\n".join(f.message for f in findings)
    assert "ragged_all_to_all" in msgs
    assert "jax.experimental.shard_map" in msgs
    assert "partial-auto" in msgs


def test_linter_rules_registry_complete():
    """Every rule the docs promise exists and is enforced by default."""
    assert set(ast_lint.RULES) == {
        "host-sync", "banned-api", "internal-api", "broad-except",
        "traced-branch"}
