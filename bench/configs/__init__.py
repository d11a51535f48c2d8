"""Model configurations (``<name>.json``, the sizes as run) and their plain
references (``<reference>.py``, named by each configuration's
``reference`` key)."""
