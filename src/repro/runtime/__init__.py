from repro.runtime.fault import RetryPolicy, StragglerWatchdog  # noqa: F401
from repro.runtime.compile_cache import enable_compile_cache  # noqa: F401
