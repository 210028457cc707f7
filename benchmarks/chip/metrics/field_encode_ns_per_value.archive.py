"""Device nanoseconds of ``jit_field_encode`` per value the program
quantised in the window (its counter ``codec.values_encoded``): the
encode kernel's cost per value, whatever the codec's bits."""
from benchmarks.chip.stages import device_ns_per_value


def read(ctx):
    return device_ns_per_value(ctx, "jit_field_encode",
                               "codec.values_encoded")
