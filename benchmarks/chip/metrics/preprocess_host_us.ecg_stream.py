"""Host time of the program's pre-processing stage per request: the
median, over the traced window's requests, of the program's own span
``ecg.preprocess`` (the dispatch of the jitted chain with its argument
transfers), which the program records in the histogram
``ecg.preprocess_us`` while a profiler session is open.  The driver
makes one pre-processing call per request, whatever the batch, so the
last ``attempted - failed`` samples are the window's; set-up's warm call
runs before the session opens.  ``None`` where the program keeps no such
histogram, or where it holds fewer samples than the window completed
requests."""
import statistics


def read(run):
    from repro.obs import metrics

    hist = metrics.registry().get("ecg.preprocess_us")
    done = run.record.attempted - run.record.failed
    if hist is None or done <= 0 or hist.dropped or len(hist.samples) < done:
        return None
    return statistics.median(hist.samples[-done:])
