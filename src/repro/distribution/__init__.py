"""Point sharding (``sharding``) and the int8 error-feedback ring for the
sharded drivers' statistics (``compression``)."""
