"""Seconds from the start of the process to the opening of the window:
start-up, weights, service, context histories and warm calls (and, in a
run that compiles, compilation)."""


def read(obs):
    return obs["setup_s"]
