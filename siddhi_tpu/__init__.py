"""siddhi_tpu — a TPU-native streaming Complex Event Processing framework.

A ground-up re-design of the capabilities of Siddhi (the reference CEP engine)
for TPU hardware: SiddhiQL-compatible queries are compiled — not interpreted —
into batched, columnar programs; pattern/sequence queries become NFA transition
tables stepped with JAX kernels over thousands of partitions at once; state
lives in device arrays sharded over a `jax.sharding.Mesh`.

Public API mirrors the reference's entry points:

    from siddhi_tpu import SiddhiManager, StreamCallback, QueryCallback, Event

    manager = SiddhiManager()
    runtime = manager.create_siddhi_app_runtime('''
        define stream StockStream (symbol string, price float, volume long);
        @info(name='q1')
        from StockStream[price > 100.0]
        select symbol, price insert into HighPrice;
    ''')
    runtime.add_callback("HighPrice", StreamCallback(print))
    runtime.start()
    runtime.get_input_handler("StockStream").send(["IBM", 101.0, 10])
"""

__version__ = "0.1.0"

from .analysis import AnalysisResult, Diagnostic, analyze
from .compiler import SiddhiCompiler
from .core.event import Event, EventChunk
from .core.runtime import SiddhiAppRuntime, SiddhiManager
from .core.statistics import StatisticsManager, prometheus_text
from .core.tracing import Tracer, disable_tracing, enable_tracing, tracer
from .core.snapshot import (FileSystemPersistenceStore,
                            InMemoryPersistenceStore, PersistenceStore)
from .core.source_sink import InMemoryBroker
from .core.stream import (ColumnarStreamCallback, QueryCallback,
                          StreamCallback)
from .query_api import (Annotation, AttrType, Expression, Query, Selector,
                        SiddhiApp, StreamDefinition)

__all__ = [
    "SiddhiManager", "SiddhiAppRuntime", "SiddhiCompiler",
    "Event", "EventChunk", "StreamCallback", "ColumnarStreamCallback",
    "QueryCallback",
    "InMemoryBroker", "PersistenceStore", "InMemoryPersistenceStore",
    "FileSystemPersistenceStore",
    "SiddhiApp", "StreamDefinition", "Query", "Selector", "Expression",
    "Annotation", "AttrType",
    "StatisticsManager", "prometheus_text",
    "Tracer", "tracer", "enable_tracing", "disable_tracing",
    "analyze", "AnalysisResult", "Diagnostic",
]
