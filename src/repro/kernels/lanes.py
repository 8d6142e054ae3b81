"""Row <-> column relayouts inside Pallas TPU kernels.

A per-row statistic is computed as an [n, 1] column (n on sublanes) and
stored lane-dense as a [1, n] row, and the reverse for a per-row input.
Mosaic lowers the change through a transpose of a full 128-lane tile."""
import jax.numpy as jnp

LANES = 128


def col_to_row(x):
    """[n, 1] column -> [1, n] row."""
    return jnp.transpose(jnp.broadcast_to(x, (x.shape[0], LANES)))[:1]


def row_to_col(x):
    """[1, n] row -> [n, 1] column."""
    return jnp.transpose(jnp.broadcast_to(x, (LANES, x.shape[1])))[:, :1]
