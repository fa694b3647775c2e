from repro.serve.config import (AutotuneConfig, EngineConfig,  # noqa: F401
                                MemoryConfig, ResilienceConfig,
                                SamplingParams, SchedulerConfig,
                                SpeculativeConfig)
from repro.serve.engine import Engine, Request  # noqa: F401
from repro.serve.faults import Fault, FaultError, FaultPlan  # noqa: F401
from repro.serve.paged import PagedCache  # noqa: F401
from repro.serve.resilience import (DEGRADE_LADDER, Backoff,  # noqa: F401
                                    Guardrail, Health, Watchdog)
from repro.serve.tracing import Tracer  # noqa: F401
