"""Page pool and slots (``PagePool``): share of the pages reserved by the
live lanes of a tick that hold a written token -- the sum of the
``pages_written`` args of the program's ``engine.decode_tick`` spans over
the sum of their ``pages_reserved`` (traced run).  Admission reserves a
request's whole page budget up front; the rest waits to be written."""


def read(run):
    reserved = written = 0
    for name, _, _, args in run.spans:
        if name == "engine.decode_tick" and "pages_reserved" in args:
            reserved += args["pages_reserved"]
            written += args["pages_written"]
    return 100.0 * written / reserved if reserved else None
