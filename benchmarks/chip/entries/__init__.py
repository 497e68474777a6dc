"""Entries: what each cell's timed window drives (``<entry>.py``, class
``Entry`` with ``setup``, ``window``, ``release`` and ``verify``)."""
