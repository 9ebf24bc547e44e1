"""Engines take weights with the leading model axis M=1.  Tests build
unstacked trees, which their reference forwards read, and give each
engine the stacked view of the same values."""
import jax


def with_model_axis(params):
    """``params`` with the leading model axis M=1 that ``Engine`` takes."""
    return jax.tree_util.tree_map(lambda a: a[None], params)
