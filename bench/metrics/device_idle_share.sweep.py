"""Share of the traced window in which no operation ran on the device
(sweep cells): 100 x (1 - union of operation intervals / window)."""


def read(view):
    return view.idle_share()
