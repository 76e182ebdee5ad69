"""Device time a step in collectives the chip compiler fused into another
operation: class `collective_fused` of harness/trace/classes.py (a fusion
whose `hlo_category` names a collective, "all-reduce-scatter fusion": the
reduce-scatter behind a row-parallel projection; or an `async-collective`
fusion), in every region and in none. By their names these are `fusion`s:
`collective_exposed_pct`, which matches names, cannot see them."""

from benchmark.harness.trace import classes


def read(run):
    if not run.trace or run.trace["devices"] < 2:
        return None
    return classes.class_ms(run, classes.COLLECTIVE_FUSED)
