"""Answered, correct queries per second over the whole window. The window
runs from the first send to the last answer of the requests that were sent
within --seconds, so no request is cut and none is counted that was not
answered; failed and wrong answers count as missing."""


def read(ctx):
    return (len(ctx["requests"]) - ctx["answers_wrong"]) / ctx["window_s"]
