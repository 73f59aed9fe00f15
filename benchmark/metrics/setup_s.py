"""Process start to the first timed step or request, compilation included."""


def read(facts):
    return facts["setup_s"]
