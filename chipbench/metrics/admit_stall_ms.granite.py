"""How much later a step's tokens arrive when an admission (one prompt of up
to 768 tokens, a request every other step) ran on the device before it:
median arrival-to-arrival of consecutive step dispatches with an admission's
``seq`` between theirs, less the median without (the program's
``mx:serve:step`` / ``mx:serve:route`` spans inside the window)."""
from chipbench import program_trace


def read(run):
    return program_trace.admit_stall_ms(run)
