"""Readers over the split of set-up that ``run.py`` times itself and
the compile log it copies from ``chip_smoke.CompileLog``."""


def field(readings, name: str):
    return readings["setup"].get(name)
