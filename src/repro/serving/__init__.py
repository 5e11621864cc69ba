from repro.serving.scheduler_service import (AdmissionError,
                                             SchedulerService,
                                             TransientRejection,
                                             WorkflowHandle)
