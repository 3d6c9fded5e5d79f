"""Plain float32 references that decide a run's ``correct``.

Nothing here imports the program (``src/repro``): the references are
written from the published descriptions of the models, of AdamW and of
the paper's B128/DE quantizer.
"""
