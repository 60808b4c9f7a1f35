"""Model step: device time of the prefill program in the traced window,
over the prefill chunks the window's steps ran."""
import layers


def read(run):
    runs = layers.program_runs(run)
    chunks = sum(1 for s in run.steps if s.prefill_tokens)
    if runs is None or not runs[1] or not chunks:
        return None
    return layers.seconds(runs[1]) / chunks * 1e3
