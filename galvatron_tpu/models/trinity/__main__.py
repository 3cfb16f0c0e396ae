from galvatron_tpu.models.trinity import main

raise SystemExit(main())
