"""Rotary position embedding on adjacent channel pairs
(``_contrib_rotary_embedding``): what ``rope_interleave`` means in the
published configurations of latent-attention models.

Pair ``i`` of the rotated channels (channels ``2i``, ``2i + 1`` of them)
turns at position ``t`` by ``phi = t * theta ** (-2i / width)``::

    (x_2i, x_2i+1) -> (x_2i cos phi - x_2i+1 sin phi,
                       x_2i sin phi + x_2i+1 cos phi)

in place, no scaling.  Angles, sines and cosines are float32 from integer
positions whatever the data's dtype: at ``theta`` 3.2e7 the slowest of 32
pairs turns 5.4e-8 rad a token and the fastest one radian, which bfloat16
positions or angles would lose.  The rotation itself is float32 and the
result has the data's dtype.  (``gluon.model_zoo.llama`` rotates halves, as
its family is published, from ``F`` operators.)

``halves=True`` is the other convention, that of a partial rotary factor in
Qwen3-Next's gated attention: the FIRST ``rotary_dim`` channels turn,
channel ``i`` paired with ``i + rotary_dim / 2`` at the same rate ``theta **
(-2i / rotary_dim)``, and the channels after them pass as they are.
"""
from __future__ import annotations

import jax.numpy as jnp

from .registry import register


def rope_angles(t, width, theta):
    """``(cos, sin)``, each ``(t, width)`` float32, at positions ``0 .. t -
    1``: ``cos`` holds ``cos phi`` at both channels of a pair, ``sin`` holds
    ``-sin phi`` at the first and ``sin phi`` at the second."""
    pair = jnp.arange(width, dtype=jnp.int32) // 2
    rate = jnp.float32(theta) ** (-2.0 * pair.astype(jnp.float32) / width)
    phi = jnp.arange(t, dtype=jnp.int32).astype(jnp.float32)[:, None] * rate
    sign = jnp.where(jnp.arange(width) % 2 == 0, -1.0, 1.0)
    return jnp.cos(phi), jnp.sin(phi) * sign.astype(jnp.float32)


def rotate_pairs(x, cos, sin):
    """``x (..., t, width)`` rotated by ``rope_angles``' ``cos`` and ``sin``:
    ``x * cos + partner(x) * sin``, the partner of a channel being its
    neighbour in the pair (two shifts along the lanes and a select, no
    strided access).  Float32 inside, ``x``'s dtype out."""
    f = x.astype(jnp.float32)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, jnp.roll(f, -1, axis=-1),
                        jnp.roll(f, 1, axis=-1))
    return (f * cos + partner * sin).astype(x.dtype)


def _rotate_halves(data, width, theta):
    """The first ``width`` channels of ``data (..., T, D)`` turned, channel
    ``i`` with ``i + width / 2`` at ``rope_angles``' rate of pair ``i``;
    float32 inside."""
    half = width // 2
    cos, sin = rope_angles(data.shape[-2], width, theta)
    cos, sin = cos[:, ::2], sin[:, 1::2]        # one column a pair
    a = data[..., :half].astype(jnp.float32)
    b = data[..., half:width].astype(jnp.float32)
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([turned.astype(data.dtype), data[..., width:]],
                           axis=-1)


@register("_contrib_rotary_embedding")
def rotary_embedding(data, theta=10000.0, rotary_dim=None, halves=False):
    """``data (..., T, D)`` with the last ``rotary_dim`` channels (all of
    them by default; an even number) of every row rotated at the row's
    position ``0 .. T - 1`` along the second-last axis, adjacent channels a
    pair; the channels before them pass as they are.  ``halves``: the first
    ``rotary_dim`` channels, in halves (the module's docstring).  The same
    dtype out."""
    d = data.shape[-1]
    width = d if rotary_dim is None else int(rotary_dim)
    if width % 2 or not 0 < width <= d:
        raise ValueError("rotary_embedding: rotary_dim %r of %d channels"
                         % (rotary_dim, d))
    if halves:
        return _rotate_halves(data, width, float(theta))
    cos, sin = rope_angles(data.shape[-2], width, float(theta))
    turned = rotate_pairs(data[..., d - width:], cos, sin)
    if width == d:
        return turned
    return jnp.concatenate([data[..., :d - width], turned], axis=-1)
