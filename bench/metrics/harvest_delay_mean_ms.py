"""Runtime: how long a finished forward waits for the host to harvest it.
Per forward in the window, the start of its ``harvest`` span minus the
end of its extract program on the device; the mean, ms (device trace).
A mean, because a busy cell runs about 17 forwards in its window."""
import spans


def read(run):
    return spans.harvest_delay_mean_ms(spans.of_run(run))
