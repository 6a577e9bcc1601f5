"""Set-up: from process start to the window's opening — weights drawn on the
device, the warm set served (compiles or cache loads), the ramp."""


def read(rec):
    return rec.setup_s
