from galvatron_tpu.models.lfm2 import main

raise SystemExit(main())
