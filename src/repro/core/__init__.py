"""Focus core: the paper's contribution (ingest/query split, top-K index,
clustering, parameter selection, specialization)."""
from repro.core.index import (  # noqa: F401
    ClassMap,
    Cluster,
    ClusterStore,
    TopKIndex,
    OTHER,
)
from repro.core.engine import (  # noqa: F401
    BatchQueryStats,
    EngineStats,
    QueryEngine,
)
from repro.core.archive import (  # noqa: F401
    ArchiveBatchStats,
    ArchiveQueryEngine,
    ArchiveQueryResult,
    ShardCatalog,
    ShardLoader,
    ShardMeta,
)
from repro.core.ingest import IngestConfig, IngestStats, ingest  # noqa: F401
from repro.core.pipeline import (  # noqa: F401
    PipelineStats,
    batch_bucket,
    staged_cheap_apply,
)
from repro.core.streaming import (  # noqa: F401
    IngestDelta,
    MultiStreamRunner,
    StreamingIngestor,
)
from repro.core.query import (  # noqa: F401
    BaselineCosts,
    QueryResult,
    dominant_classes,
    gt_frames_by_class,
    gpu_seconds,
    precision_recall,
    query,
)
