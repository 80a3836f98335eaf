"""setup_s: seconds from the start of the process to the window's open:
loading, making the designs, compiling or loading programs, warming up."""


def read(m):
    return m.setup_s
