"""solve_p50_ms.solve: the median of the window's solve times, in ms (host
clock). ``solve_ms`` takes all the work and all the time of the window, so
a single stall of the host or the device moves it; the median beside it
does not, and shows the solver's own pace."""
import statistics


def read(record, suffix):
    times = record.get("solve_times_ms")
    return statistics.median(times) if times else None
