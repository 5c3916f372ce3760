"""Device time per execution of the decode-step program (the one that runs the decode-attention kernel), in ms, from the trace."""

from bench import readers


def read(rec):
    return readers.decode_step_ms(rec)
