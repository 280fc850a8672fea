"""mxnet_tpu.serving — batched inference-serving runtime.

The deployment half of the framework (reference analogue:
``c_predict_api.cc`` + the model-server ecosystem around it): load a
frozen :class:`~mxnet_tpu.stablehlo.ServedModel` (or any hybridizable
Block), put a :class:`DynamicBatcher` in front of the shape-bucketed
:class:`InferenceEngine`, and serve under load with admission control,
deadline shedding and a live metrics snapshot.

Typical stack::

    engine  = serving.InferenceEngine(net, batch_buckets=(1, 2, 4, 8, 16))
    batcher = serving.DynamicBatcher(engine, max_batch_size=16,
                                     max_delay_ms=2.0, max_queue=128)
    with serving.ModelServer(batcher, port=0) as srv:
        client = serving.ServingClient(srv.url)
        y = client.predict(x, deadline_ms=100, max_retries=3)
        print(client.stats()["latency"])

Fleet scale (``fleet.py``): ``ReplicaSupervisor`` runs N such stacks as
supervised worker processes and ``Router`` load-balances across them
with transparent retry, per-replica circuit breakers, hedged dispatch,
fleet-level shedding and zero-drop rolling weight swaps; ``Autoscaler``
(``autoscaler.py``) resizes the fleet off the federated gauges through
the same zero-drop drain machinery (``tests/test_fleet.py`` holds the
chaos proofs: nothing accepted is lost).

Generative serving (``generate.py``): :class:`GenerationEngine` runs
KV-cached incremental decode with continuous batching — one
shape-bucketed prefill program plus one fixed-shape decode program over
the whole in-flight batch, requests joining and leaving at token
boundaries — served through the same ``ModelServer``/``Router`` stack
as a streaming ``/generate`` endpoint whose token lines one thread a
server writes (``stream_writer.py``; docs/SERVING.md "Generative
serving").

See ``docs/SERVING.md`` for architecture and knobs; tokens/s and the gap
between tokens are measured on the chip by ``chipbench/`` (PERF.md).
"""
from .errors import (ServingError, QueueFullError,  # noqa: F401
                     DeadlineExceededError, EngineClosedError,
                     ServiceUnavailableError, GenerationStreamBroken)
from .metrics import (LatencyHistogram, ServingMetrics,  # noqa: F401
                      histogram_expo)
from .engine import InferenceEngine  # noqa: F401
from .batcher import DynamicBatcher, Request  # noqa: F401
from .http import ModelServer, encode_array, decode_array  # noqa: F401
from .client import ServingClient  # noqa: F401
from .fleet import (ReplicaSpec, ReplicaSupervisor,  # noqa: F401
                    Router, RouterServer, federation_prometheus_text)
from .autoscaler import Autoscaler  # noqa: F401
from .generate import (GenerationEngine, GenerationMetrics,  # noqa: F401
                       GenerationStream)

__all__ = [
    "ServingError", "QueueFullError", "DeadlineExceededError",
    "EngineClosedError", "ServiceUnavailableError",
    "GenerationStreamBroken", "LatencyHistogram",
    "ServingMetrics", "histogram_expo", "InferenceEngine",
    "DynamicBatcher", "Request", "ModelServer", "ServingClient",
    "encode_array", "decode_array", "ReplicaSpec", "ReplicaSupervisor",
    "Router", "RouterServer", "federation_prometheus_text", "Autoscaler",
    "GenerationEngine", "GenerationMetrics", "GenerationStream",
]
