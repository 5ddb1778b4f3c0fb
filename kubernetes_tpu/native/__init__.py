"""Native components (C++, loaded via ctypes).

The compute path is JAX/XLA/Pallas; the runtime around it uses C++ where
the reference's equivalent is native. Currently:

    walcore.cc   — the store's WAL appender (etcd's wal/ analog)

Builds are lazy: `build.load(name)` compiles with g++ on first use and
keeps the library next to the source under the source's content hash.
Every consumer carries a pure-python implementation for hosts without a
toolchain; a failed build is logged, and the consumer reports which path
is active.
"""

from .build import load

__all__ = ["load"]
