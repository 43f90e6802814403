"""`decode_state_rows_live_share` for granite-4.0-h-micro.rag: slots
decoding / `max_batch` over the window's decoding iterations
(`Engine.stats` `state_rows_live_sum` / `state_rows_sum`), here of 48 slots
whose 75.5 MB of state and rows a step moves whichever are live: what a
step that skipped idle rows would save of `decode_ssm_ms`."""
from benchmarks.harness import manifest


def read(run):
    return manifest.layer_reader("decode_state_rows_live_share")(run)
