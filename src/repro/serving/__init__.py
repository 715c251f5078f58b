from repro.serving.engine import Request, validate_request  # noqa: F401
from repro.serving.faults import (FaultInjected,  # noqa: F401
                                  FaultInjector)
from repro.serving.service import (ServiceResult, ServiceSLA,  # noqa: F401
                                   ServiceTicket, SimulationService,
                                   build_ladder)
