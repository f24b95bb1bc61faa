"""setup_s: seconds from process start to the measured window.

Loading, making the keys on the device, and warming every shape the cell
uses: compiling on a checkout's first run, loading compiled programs from
the persistent cache on every later one."""


def read(run):
    return run.setup_s
