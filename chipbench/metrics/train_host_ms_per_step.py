"""Host milliseconds of ``SPMDTrainer.step`` a step: its ``mx:train:feed`` and
``mx:train:step`` spans inside the window, summed, over the steps."""
from chipbench import program_trace


def read(run):
    return program_trace.host_ms_per_dispatch(run, "mx:train:",
                                              "mx:train:step")
