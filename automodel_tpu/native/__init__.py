"""Native (C++) data-plane core, loaded lazily via ctypes.

``lib()`` compiles ``src/packing.cpp`` on first use into a cached shared
object and returns the ctypes handle, or None when no C++ compiler is on
PATH — the one case where callers run the Python reference
implementations; a failed build or load raises.  ``source()`` says which
happened ("built" / "cached" / "python").
"""

from automodel_tpu.native.build import available, lib, source

__all__ = ["available", "lib", "source"]
