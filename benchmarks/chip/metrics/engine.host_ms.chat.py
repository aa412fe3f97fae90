"""engine.host_ms.chat: the host's own milliseconds per engine step in the
traced window: the step's time less its waits on device results, work the
device waits through unless a program is still queued (engine counters
``step_host_s`` and ``step_calls``)."""
from chipbench.spans import counted_host_ms


def read(ctx):
    return counted_host_ms(ctx)
