"""Native (C++) components of the runtime.

The compute path is JAX/XLA; the IO-side hot paths are native:
csvparse.cpp replaces the JVM CsvProducer + Jackson parsing layer of the
reference (producer/CsvProducer.java, serialization/JSONSerde.java) with
a one-pass C++ CSV → CSR parser exposed through ctypes (binding.py).
Everything degrades gracefully to the pure-Python path when the shared
library is unavailable.
"""

from kafka_ps_tpu.native.binding import (  # noqa: F401
    NativeCsv,
    is_available,
    parse_csv,
    status,
)
