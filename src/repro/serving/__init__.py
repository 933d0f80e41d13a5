from repro.serving.engine import (InferenceEngine, PagePoolExhausted,  # noqa: F401
                                  RequestRefused)
from repro.serving.hibernation import HibernationStore  # noqa: F401
from repro.serving.scheduler import QoSScheduler, Request, SchedulerStats  # noqa: F401
from repro.serving.plane import (ServingPlane, PlaneResult, PlaneLoad,  # noqa: F401
                                 RealEngineBackend, SimulatedEngine)
