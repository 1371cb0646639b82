"""Compile events (the program's ``compilestats`` trace counters) inside
the window.  Set-up warms every shape the window uses, so this reads 0."""


def read(obs):
    return obs.window_compiles
