"""One driver for each kind of job; a traffic file names its job."""
