"""Process start to the first timed request."""


def read(cap):
    return cap.setup_s
