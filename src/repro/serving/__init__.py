from repro.serving.engine import InferenceEngine, ServingEngine
from repro.serving.kv_cache import BlockAllocator
from repro.serving.loadgen import (ArrivalSpec, LoadSpec, PromptSpec,
                                   SLOSpec, TimedTask, arrival_times,
                                   make_trace, replay)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.runner import DecodeHandle, ModelRunner
from repro.serving.sampling import GREEDY, SamplingParams, validate_sampling
from repro.serving.scheduler import (ChunkedPrefillPolicy, DeadlinePolicy,
                                     FCFSPolicy, PriorityPolicy,
                                     SchedulerPolicy, make_policy)
from repro.serving.spec import (DraftState, SpecConfig, resolve_draft,
                                spec_support_reason)
from repro.serving.stats import (EngineStats, Reservoir, percentile,
                                 percentiles)
from repro.serving.tasks import (EncodeTask, GenerateTask, Rejection,
                                 Request, Task, TokenEvent, validate_task)
from repro.serving.trace import (Tracer, prometheus_text,
                                 validate_chrome_trace)
