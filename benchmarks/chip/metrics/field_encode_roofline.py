"""Share of the HBM roofline the field encode reached: the bytes of
quantising every value archived in the window (4 + bits/8 per value),
over the device time of ``jit_field_encode`` at 819 GB/s."""


def read(ctx):
    return ctx.roofline("jit_field_encode", "encode_hbm_bytes")
