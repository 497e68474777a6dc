"""Traffic: general generators (``<kind>.py``) and the mixes they read
(``<mix>.json``)."""
