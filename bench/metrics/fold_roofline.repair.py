"""Roofline share of the data plane's segment-XOR fold, in %.

Algorithmic bytes of every `xor_reduce_segments` call in the window
(`roofline.fold_bytes`) at the HBM peak of `peaks.json`, over the device
time of every op that ran inside those calls' host spans (the zero-row
concatenation, the gather and the kernel).
"""
import roofline


def read(ctx):
    return roofline.step_share(ctx, "fold")
