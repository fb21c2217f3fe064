"""A percentile (``params["q"]``) of submit -> first token over the
requests submitted in the window whose first token came inside it, from
the driver's own stamps.  Inside it, because a traced run stops the
profiler (seconds on the host) between the window and the drain, and a
request in prefill then would read that.  A traced window is short: it
holds about ten such requests."""

from chipbench import stats


def read(observed, params):
    if observed.get("kind") != "serve" or \
            not observed.get("ttft_in_window_s"):
        return None
    return stats.percentile(observed["ttft_in_window_s"],
                            params["q"]) * 1e3
