"""The benchmark's own library: the window, the reduction from traces and
spans to numbers, the roofline arithmetic, the comparison that decides
``correct``. Nothing here imports the program."""
