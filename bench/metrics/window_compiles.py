"""Engine: ``EngineStats.misses`` of both step programs during the window.
Set-up compiles every signature, so this should read 0."""


def read(rec):
    return rec.compiles
