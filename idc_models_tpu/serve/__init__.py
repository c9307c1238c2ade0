from idc_models_tpu.serve.api import (  # noqa: F401
    LMServer, Request, Result, load_trace, poisson_trace, save_trace,
)
from idc_models_tpu.serve.brownout import BrownoutController  # noqa: F401
from idc_models_tpu.serve.cluster import (  # noqa: F401
    AutoscaleConfig, Autoscaler, ClusterTelemetry, ClusterWatchdog,
    PrefixRegistry, Replica, Router, WatchdogConfig, build_replica,
)
from idc_models_tpu.serve.compile_cache import CompileCache  # noqa: F401
from idc_models_tpu.serve.engine import SlotEngine  # noqa: F401
from idc_models_tpu.serve.faults import (  # noqa: F401
    InjectedEngineCrash, InjectedPrefillError, ServeFault,
    ServeFaultPlan, parse_serve_fault_spec,
)
from idc_models_tpu.serve.journal import (  # noqa: F401
    RequestJournal, load_journal, pending_requests,
)
from idc_models_tpu.models.draft import NGramDrafter  # noqa: F401
from idc_models_tpu.serve.metrics import ServingMetrics  # noqa: F401
from idc_models_tpu.serve.pages import (  # noqa: F401
    PageAllocator, PageExhausted,
)
from idc_models_tpu.serve.prefix_cache import (  # noqa: F401
    PagedPrefixCache, PrefixCache,
)
from idc_models_tpu.serve.scheduler import (  # noqa: F401
    AdmissionQueue, RetryPolicy, Scheduler,
)
from idc_models_tpu.serve.tenancy import (  # noqa: F401
    AdapterBank, Tenancy, TenantQuota, TenantRegistry,
)
