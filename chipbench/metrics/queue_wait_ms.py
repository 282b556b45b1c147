"""Mean milliseconds a request waited in the engine's queue for a row, over
the requests that took a row in the window: the rise of the sum over the
rise of the count of ``alpa_serving_queue_wait_seconds``.  The server's own
view of the wait that ``ttft_p90_ms`` sees from the client."""
from chipbench import counters


def read(obs):
    mean = counters.mean_observed(obs, "alpa_serving_queue_wait_seconds")
    return None if mean is None else mean * 1e3
