"""What one scoring step has to do, counted from the configuration's
sizes and the number of VALID rows — never from the padded plane, and the
same whatever later implements the step. One file per model family
(``costs/<family>.py`` with ``step_cost``), found by the configuration's
``model.family`` (``benchmark.metrics.step_cost``).
"""
