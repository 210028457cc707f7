"""Device nanoseconds of ``jit_field_decode`` per value the program
restored in the window (its counter ``codec.values_decoded``; the
training cell reads nothing back after its window, so the counter holds
the window's decodes alone)."""
from benchmarks.chip.stages import device_ns_per_value


def read(ctx):
    return device_ns_per_value(ctx, "jit_field_decode",
                               "codec.values_decoded")
