"""Share of the HBM roofline the field decode reached: the bytes of
restoring every value decoded in the window (bits/8 + 4 per value), over
the device time of ``jit_field_decode`` at 819 GB/s."""


def read(ctx):
    return ctx.roofline("jit_field_decode", "decode_hbm_bytes")
