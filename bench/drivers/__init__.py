"""One driver module per kind of traffic (``serve``, ``train``, ``fabric``)."""
