"""freeze.swap_s.train: seconds of the Algorithm-2 phase swap in the window.

Host clock around ``steps.repartition_state`` between the window's two
halves, ended by ``block_until_ready`` on the new state.  Moves
``train_tokens_per_s`` (a job swaps once per epoch).
"""


def read(ctx):
    return ctx.readings.get("swap_s")
