"""FCT query execution runtime: shape bucketing, compiled-executable caching,
batched multi-CN dispatch and the device-resident relation store (see
README.md in this directory).

Importing the runtime puts every ``repro.obs`` span on the profiler's clock:
``jax.profiler.TraceAnnotation`` becomes the span annotator, so a
``jax.profiler`` capture shows the program's spans by name on its host
plane."""
import jax

from repro.obs import set_annotator
from repro.runtime.cache import ExecutableCache, default_cache
from repro.runtime.engine import FCTEngine, default_engine
from repro.runtime.store import RelationStore

set_annotator(jax.profiler.TraceAnnotation)

__all__ = ["ExecutableCache", "FCTEngine", "RelationStore", "default_cache",
           "default_engine"]
