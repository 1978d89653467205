"""Set-up time: process start to the window's start (loading, generating the
stream, compiling or loading programs, warm panes)."""


def read(window):
    return window.setup_s
