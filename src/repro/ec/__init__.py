"""Erasure-coding substrate: GF(256) arithmetic, RS codes, stripe placement."""

from repro.ec import gf256, rs, stripe  # noqa: F401
