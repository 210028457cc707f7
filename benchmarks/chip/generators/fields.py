"""The field store's generator, the one a traffic file gets where it names
none: weather fields made on the chip (:mod:`benchmarks.chip.fields`), a
``ChunkedFieldStore`` producer and consumer, and the writer, readers and
loaders of :mod:`benchmarks.chip.load`."""
from benchmarks.chip.fields import make_fields as make_data  # noqa: F401
from benchmarks.chip.load import References, Store, Traffic  # noqa: F401
