"""Weights made from ``--seed`` by the benchmark, never by the program.

A table's value at ``(table, row, column)`` is a hash, so the program's
initializer hook can fill a slab on the device and the plain reference can
compute the few rows it needs without ever holding a table.

Two stages, because the program bakes whatever its initializer closes over
into the compiled init program: ``base_rows`` takes no seed (one program in
the compile cache whatever the seed), and ``scramble`` is an elementwise pass
over any array of weights that takes the seed as a traced argument. A weight
is ``scramble(base_rows(...), seed)`` on both sides.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_U = jnp.uint32


def _mix32(x):
    """lowbias32 integer hash (uint32 in, uint32 out)."""
    x = x ^ (x >> 16)
    x = x * _U(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * _U(0x846CA68B)
    return x ^ (x >> 16)


def _unit(h):
    """uint32 hash -> float32 in [0, 1) from its top 24 bits."""
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def table_key(table: int) -> int:
    """The constant that tells one table's hash from another's."""
    return (table * 0x9E3779B9 + 0x7F4A7C15) & 0xFFFFFFFF


def table_scale(table_rows: int) -> float:
    """``1/sqrt(rows)``: the DLRM initializer's range for a table."""
    return 1.0 / math.sqrt(table_rows)


def base_values(key, scale, rows, cols, dtype) -> jax.Array:
    """Seed-free value of the table with ``table_key`` ``key`` and
    ``table_scale`` ``scale`` at ``(rows, cols)``, elementwise over the
    arguments broadcast together: uniform in ``+-scale``, rounded to
    ``dtype``."""
    hr = _mix32(jnp.asarray(rows).astype(_U) + jnp.asarray(key, _U))
    h = _mix32(hr ^ (jnp.asarray(cols).astype(_U) * _U(0x85EBCA6B)
                     + _U(0xC2B2AE35)))
    return ((2.0 * _unit(h) - 1.0)
            * jnp.asarray(scale, jnp.float32)).astype(dtype)


def base_rows(table: int, table_rows: int, rows, col0: int, width: int,
              dtype) -> jax.Array:
    """``base_values`` of ``rows`` (int array ``[n]``) of one table, columns
    ``col0 .. col0+width``."""
    return base_values(table_key(table), table_scale(table_rows),
                       jnp.asarray(rows)[:, None],
                       jnp.arange(col0, col0 + width, dtype=_U)[None, :],
                       dtype)


def seed_words(seed: int) -> np.ndarray:
    """``--seed`` (any whole number up to a little over 2**31) as two uint32."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def scramble(values: jax.Array, words: jax.Array) -> jax.Array:
    """Seed-dependent elementwise pass: each value, as its bfloat16 bits say
    it, is multiplied by a factor in [0.75, 1.25) drawn from a hash of those
    bits and the seed. Shape and layout do not matter, so it runs over the
    program's slab as the program laid it out. The value is rebuilt from the
    bits by integer operations: a float convert there and back is one that the
    TPU compiler may skip (excess precision), and the two sides would round
    differently."""
    key = _mix32(words[0] ^ _mix32(words[1] + _U(0x632BE5AB)))
    bits = jax.lax.bitcast_convert_type(
        values.astype(jnp.bfloat16), jnp.uint16).astype(_U)
    h = _mix32(bits * _U(0x9E3779B1) ^ key)
    factor = 0.75 + 0.5 * _unit(h)
    exact = jax.lax.bitcast_convert_type(bits << 16, jnp.float32)
    return (exact * factor).astype(values.dtype)


def table_rows(table: int, rows_in_table: int, rows, width: int, dtype,
               words) -> jax.Array:
    """The weights of ``rows`` of one whole-width table for this seed."""
    return scramble(base_rows(table, rows_in_table, rows, 0, width, dtype),
                    words)


def rows_fn(sizes, width: int, dtype):
    """``rows(ids, words) -> [table rows]``: one compiled program that makes
    ``ids[t]``'s rows of every table ``t`` for the seed in ``words``."""
    @jax.jit
    def rows(ids, words):
        return [table_rows(t, int(s), ids[t], width, dtype, words)
                for t, s in enumerate(sizes)]
    return rows


def dense_params(seed: int, num_numerical: int, bottom: list, top: list,
                 num_tables: int, dim: int) -> list:
    """The MLPs' weights from the seed, float32, as ``[(kernel, bias), ...]``
    bottom layers first: Glorot-normal kernels, biases normal with variance
    1/fan_out (the DLRM reference's initializers). The bias of the last layer
    (one unit, so N(0, 1) there) is +1 or -1 instead: a draw near 0 starts the
    model calibrated on random labels, its first gradients are then all noise,
    and every number compared reads several times what it reads on the other
    seeds."""
    rng = np.random.default_rng([int(seed), 0xD15E])
    nf = num_tables + 1
    top_in = nf * (nf - 1) // 2 + dim
    layers = []
    for dims in ([num_numerical] + list(bottom), [top_in] + list(top)):
        for a, b in zip(dims, dims[1:]):
            k = rng.normal(0.0, math.sqrt(2.0 / (a + b)), (a, b))
            bias = rng.normal(0.0, math.sqrt(1.0 / b), (b,))
            layers.append((k.astype(np.float32), bias.astype(np.float32)))
    k, bias = layers[-1]
    layers[-1] = (k, np.where(bias < 0, -1.0, 1.0).astype(np.float32))
    return layers
