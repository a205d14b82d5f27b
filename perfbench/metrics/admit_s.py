"""Host seconds of set-up's admission calls (``submit`` and ``fill_slots``)."""


def read(run):
    return run.rec.phases.get("admit_s")
