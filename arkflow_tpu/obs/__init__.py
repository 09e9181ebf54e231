from arkflow_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    global_registry,
)
from arkflow_tpu.obs.trace import (  # noqa: F401
    Span,
    TraceContext,
    Tracer,
    TracingConfig,
    activate,
    global_tracer,
    record_stage,
    stage_span,
)
from arkflow_tpu.obs.startup import (  # noqa: F401
    cold_step,
    setup_stage,
    startup_report,
)
