from .batching import AssignRequest, FitRequest, ServeMetrics, pack_batches
from .cluster_server import ClusterServer, ModelRegistry
