"""Roofline share of the data plane's GF(256) premultiply, in %.

Algorithmic bytes of every `gf256_scale_batch` call in the window
(`roofline.premultiply_bytes`) at the HBM peak of `peaks.json`, over the
device time of every op that ran inside those calls' host spans. The
step moves bytes and does no matmul, so the bandwidth bound is its
roofline.
"""
import roofline


def read(ctx):
    return roofline.step_share(ctx, "premultiply")
